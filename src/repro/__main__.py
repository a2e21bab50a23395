"""``python -m repro run`` -- the canonical launcher (DESIGN.md S10).

One command drives every execution mode from a single serializable
``RunSpec``: pass a spec JSON file, or build one from flags.  The run's
record JSON and checkpoint both embed the canonical serialized spec, so
any result is replayable from one blob:

    # declaratively, from a spec document
    python -m repro run spec.json --record results/

    # or from flags (prints/records the equivalent spec)
    python -m repro run --n 64 --m 64 --engine multispin \\
        --temperature 2.27 --seed 7 --n-measure 100 --measure-every 2

    # validate + print the dispatch plan, no device work
    python -m repro run spec.json --dry-run

    # device ops and the program's repro.* spans in one profile
    python -m repro run spec.json --sweeps 100 --profile prof/

    # resume a checkpoint (single, ensemble, or sharded -- the spec
    # inside the file picks the runner)
    python -m repro run --restore ckpt.npz --sweeps 500
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np


def _build_spec(args) -> "RunSpec":
    from repro.api import (BatchSpec, EngineSpec, LatticeSpec, MeshSpec,
                           RunSpec, SweepSpec)
    if args.spec:
        with open(args.spec) as f:
            spec = RunSpec.from_json(f.read())
        return spec
    params = {}
    if args.tc_block is not None:
        params["tc_block"] = args.tc_block
    if args.p_ferro is not None:
        params["p_ferro"] = args.p_ferro
    sweep = None
    if args.n_measure:
        sweep = SweepSpec(thermalize=args.thermalize,
                          measure_every=args.measure_every,
                          n_measure=args.n_measure,
                          fields=tuple(args.fields.split(",")))
    batch = None
    if args.temps:
        temps = tuple(float(t) for t in args.temps.split(","))
        seeds = tuple(int(s) for s in args.seeds.split(",")) \
            if args.seeds else None
        batch = BatchSpec(temperatures=temps, seeds=seeds,
                          grid=args.grid)
    mesh = None
    if args.mesh:
        shape = tuple(int(d) for d in args.mesh.split("x"))
        names = tuple(args.mesh_axes.split(",")) if args.mesh_axes \
            else tuple(f"ax{i}" for i in range(len(shape)))
        mesh = MeshSpec(shape=shape, axis_names=names)
    return RunSpec(
        lattice=LatticeSpec(n=args.n, m=args.m or args.n,
                            init_p_up=args.init_p_up),
        engine=EngineSpec(name=args.engine, params=params),
        temperature=args.temperature, seed=args.seed,
        sweep=sweep, batch=batch, mesh=mesh)


def _summarize(traj: dict) -> dict:
    """Scalar summary of a measured trajectory (per-field mean of the
    final half -- a cheap steady-state estimate for the run log)."""
    out = {}
    for k, v in traj.items():
        tail = np.asarray(v)[len(v) // 2:]
        out[f"{k}_mean"] = float(np.mean(tail))
        out[f"abs_{k}_mean"] = float(np.mean(np.abs(tail)))
    return out


def _finish_trace(args, spec) -> None:
    if not args.trace:
        return
    import repro.telemetry as tel
    path = tel.export(args.trace,
                      meta={"engine": spec.engine.name,
                            "mode": spec.mode,
                            "lattice": [spec.lattice.n, spec.lattice.m],
                            "spec_json": spec.to_json()})
    print(f"# wrote trace {path} "
          f"(inspect: python -m repro.telemetry summarize {path})",
          file=sys.stderr)


def _cmd_supervise(args, spec) -> int:
    """The ``--supervise DIR`` path: preemption-safe supervised run
    with periodic checkpoints and auto-resume (DESIGN.md S13).  Exit 0
    on completion, 3 when preempted (progress checkpointed -- rerun
    the same command to resume)."""
    from repro.resilience import Supervisor, faults
    faults.install_from_env()  # CI chaos: REPRO_FAULTS JSON plan
    if not args.sweeps:
        print("--supervise needs --sweeps N (the run target)",
              file=sys.stderr)
        return 2
    sup = Supervisor(spec, args.supervise,
                     every_sweeps=args.ckpt_every_sweeps,
                     every_seconds=args.ckpt_every_seconds,
                     chunk=args.chunk, keep=args.keep)
    if sup.resumed_from is not None:
        print(f"# resumed from step {sup.resumed_from} "
              f"in {args.supervise}")
    res = sup.run(args.sweeps)
    print(f"supervised run {res.status} at sweep {res.step_count}/"
          f"{args.sweeps}; checkpoints written: "
          f"{res.checkpoints_written}")
    print(f"final_state_digest={res.digest}")
    _finish_trace(args, spec)
    return 0 if res.completed else 3


def cmd_run(args) -> int:
    if not args.profile:
        return _run(args)
    import jax
    # one profile holds the device ops and the repro.* spans
    with jax.profiler.trace(args.profile):
        rc = _run(args)
    print(f"# wrote profile under {args.profile} (open in Perfetto or "
          "xprof)", file=sys.stderr)
    return rc


def _run(args) -> int:
    from repro.api import Session, describe

    if args.trace:
        import repro.telemetry as tel
        tel.enable()
    session = None
    if args.restore and not args.dry_run:
        session = Session.restore(args.restore)  # ONE checkpoint read
        spec = session.spec
    elif args.restore:
        from repro.api.session import load_spec
        spec = load_spec(args.restore)           # spec entry only
    else:
        spec = _build_spec(args)

    if args.out_spec:
        with open(args.out_spec, "w") as f:
            f.write(spec.to_json(indent=1) + "\n")
        print(f"# wrote spec {args.out_spec}")

    plan = describe(spec)
    if args.dry_run:
        print(json.dumps(plan, indent=1, sort_keys=True))
        print(f"# dry run OK: mode={plan['mode']} "
              f"engine={plan['engine']} "
              f"lattice={plan['lattice'][0]}x{plan['lattice'][1]} "
              f"batch={plan['batch_size']}", file=sys.stderr)
        _finish_trace(args, spec)
        return 0

    if args.supervise:
        return _cmd_supervise(args, spec)

    if session is None:
        session = Session.open(spec)
    rows = []
    if spec.sweep is not None:
        import time
        t0 = time.perf_counter()
        traj = session.measure()
        dt = time.perf_counter() - t0
        summary = _summarize(traj)
        rows.append(("measure", dt * 1e6, summary))
        print(f"measured {spec.sweep.n_measure} samples "
              f"({spec.sweep.total_sweeps} sweeps) in {dt:.2f}s: " +
              " ".join(f"{k}={v:.4f}" for k, v in summary.items()))
    if args.sweeps:
        import time
        t0 = time.perf_counter()
        session.run(args.sweeps)
        mag = session.magnetization()  # blocks: honest timing boundary
        dt = time.perf_counter() - t0
        rows.append(("run", dt * 1e6,
                     {"sweeps": args.sweeps,
                      "mean_abs_m": float(np.mean(np.abs(mag)))}))
        print(f"ran {args.sweeps} sweeps in {dt:.2f}s; |m| = "
              f"{np.mean(np.abs(mag)):.4f}")
    if not rows:
        print("nothing to do: spec has no sweep plan and --sweeps is 0 "
              "(use --dry-run to just validate)", file=sys.stderr)
        _finish_trace(args, spec)
        return 2

    if args.save:
        session.save(args.save)
        print(f"# wrote checkpoint {args.save} "
              f"(step {session.step_count})")
    if args.record is not None:
        import time

        import jax

        from repro.analysis.recorder import RunRecorder
        from repro.perf.schema import validate_record
        rec = RunRecorder(meta={"spec": spec.to_dict(),
                                "mode": session.mode,
                                "step_count": session.step_count,
                                "stamp": time.strftime("%Y%m%d_%H%M%S"),
                                "backend": jax.default_backend(),
                                "device_count": jax.device_count()})
        for name, us, derived in rows:
            rec.record(name, us, spec=spec.to_json(), **derived)
        # CLI records obey the same perf-record schema as the bench
        # harness, so they diff/gate/trend interchangeably
        validate_record({"meta": rec.meta, "rows": rec.rows})
        path = rec.write_json(args.record)
        print(f"# wrote record {path}")
    _finish_trace(args, spec)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro",
        description="Unified RunSpec launcher for the Ising study")
    sub = ap.add_subparsers(dest="cmd", required=True)
    run = sub.add_parser(
        "run", help="execute (or --dry-run validate) a RunSpec",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    run.add_argument("spec", nargs="?", default="",
                     help="RunSpec JSON file (flags below are ignored "
                          "for spec construction when given)")
    run.add_argument("--dry-run", action="store_true",
                     help="parse + validate + print the dispatch plan; "
                          "no device work")
    # lattice / engine construction flags
    run.add_argument("--n", type=int, default=64)
    run.add_argument("--m", type=int, default=0,
                     help="lattice cols (default: --n)")
    run.add_argument("--init-p-up", type=float, default=0.5)
    run.add_argument("--engine", default="multispin")
    run.add_argument("--temperature", type=float, default=2.0)
    run.add_argument("--seed", type=int, default=1234)
    run.add_argument("--tc-block", type=int, default=None)
    run.add_argument("--p-ferro", type=float, default=None)
    # measurement schedule
    run.add_argument("--thermalize", type=int, default=0)
    run.add_argument("--measure-every", type=int, default=1)
    run.add_argument("--n-measure", type=int, default=0,
                     help="samples to record (0: plain --sweeps run)")
    run.add_argument("--fields", default="m,e")
    # ensemble batch
    run.add_argument("--temps", default="",
                     help="comma list -> BatchSpec (ensemble mode)")
    run.add_argument("--seeds", default="",
                     help="comma list of member seeds")
    run.add_argument("--grid", action="store_true",
                     help="temps x seeds cross product")
    # device mesh
    run.add_argument("--mesh", default="",
                     help="device mesh shape, e.g. 2x4 (sharded mode)")
    run.add_argument("--mesh-axes", default="",
                     help="comma list of mesh axis names")
    # execution / outputs
    run.add_argument("--sweeps", type=int, default=0,
                     help="plain sweeps to run (besides any sweep plan)")
    run.add_argument("--save", default="", help="checkpoint path to write")
    run.add_argument("--restore", default="",
                     help="checkpoint to resume (overrides spec/flags)")
    # supervised (fault-tolerant) execution
    run.add_argument("--supervise", default="", metavar="DIR",
                     help="run under the resilience supervisor: "
                          "periodic verified checkpoints into DIR, "
                          "SIGTERM/SIGINT-safe, auto-resume from the "
                          "newest valid step (exit 3 = preempted, "
                          "rerun to resume)")
    run.add_argument("--ckpt-every-sweeps", type=int, default=0,
                     help="supervisor checkpoint cadence in sweeps "
                          "(0: off)")
    run.add_argument("--ckpt-every-seconds", type=float, default=0.0,
                     help="supervisor checkpoint cadence in wall-clock "
                          "seconds (0: off)")
    run.add_argument("--chunk", type=int, default=64,
                     help="supervisor sweep-chunk between control "
                          "points (fixed grid: part of the bit-exact-"
                          "resume contract for key-based engines)")
    run.add_argument("--keep", type=int, default=3,
                     help="checkpoint steps the supervisor retains")
    run.add_argument("--out-spec", default="",
                     help="write the canonical spec JSON here")
    run.add_argument("--record", nargs="?", const=".", default=None,
                     metavar="DIR_OR_PATH",
                     help="write a RunRecorder JSON embedding the spec")
    run.add_argument("--trace", default="", metavar="PATH",
                     help="enable span tracing; write the Chrome trace "
                          "(.json, Perfetto-loadable) or .jsonl stream "
                          "+ metrics snapshot here")
    run.add_argument("--profile", default="", metavar="DIR",
                     help="run under jax.profiler.trace(DIR): the device "
                          "ops and the repro.* spans in one profile "
                          "(Perfetto / xprof)")
    run.set_defaults(fn=cmd_run)

    from repro.serve.__main__ import add_serve_args, run_server
    srv = sub.add_parser(
        "serve", help="run the fault-tolerant sweep-farm server "
                      "(exit 0 done / 3 drained-preempted)",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    add_serve_args(srv)
    srv.set_defaults(fn=run_server)

    args = ap.parse_args(argv)
    from repro import compile_cache
    compile_cache.enable()
    return args.fn(args)


if __name__ == "__main__":
    raise SystemExit(main())
