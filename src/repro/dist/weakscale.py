"""Weak-scaling bench of the sharded resident tier (DESIGN.md S15).

One row per (family, device count): a ``(D, 1)`` mesh over the first
``D`` devices with ``base_n * D`` lattice rows -- per-shard work is
constant along the axis, so ideal weak scaling is a flat us/call
column.  Every row records the sweep throughput (flips/ns), the shard
planner's decision (``halo_k``, ``sharded_resident``), the MEASURED
halo traffic per call (telemetry counter deltas -- the evidence that
the resident tier exchanges once per k sweeps instead of twice per
sweep), and the serialized ``RunSpec``, so each number is replayable
with ``python -m repro run``.

Two consumers share :func:`measure_rows`:

* ``benchmarks/run.py`` (``table6_dist_weakscale``) -- the full
  harness, whose committed ``BENCH_*.json`` baselines carry the
  ``dist_*`` rows the perf gate compares against;
* ``python -m repro.dist.weakscale --devices 2,8 --json DIR`` -- the
  standalone CLI the CI ``dist`` job runs; its record marks itself
  filtered (``meta.only = "dist"``) so the gate skips the non-dist
  baseline rows.
"""
import argparse
import os
import time
from typing import Dict, Iterable, List

#: resident family -> the registry engine that carries it
FAMILY_ENGINES = {
    "stencil": "stencil_pallas",
    "multispin": "multispin_pallas",
    "bitplane": "bitplane_pallas",
}


def measure_rows(devices: Iterable[int], *, base_n: int = 64,
                 cols: int = 128, sweeps: int = 4,
                 trials: int = 2) -> List[Dict]:
    """Time the sharded families along the weak-scaling axis.

    Returns one dict per row: ``name`` (``dist_<family>_d<D>``),
    ``us`` (mean us/call), ``times_s`` (per-trial walls), ``engine``,
    ``k`` (planner sweeps-per-exchange, 1 when demoted), ``spec``
    (serialized RunSpec), and ``derived`` (flips/ns + planner decision
    + measured per-call halo traffic).
    """
    import jax
    import repro.telemetry as tel
    from repro.api import (EngineSpec, LatticeSpec, MeshSpec, RunSpec,
                           Session)
    from repro.core.engine import ENGINES

    rows: List[Dict] = []
    for nd in devices:
        if nd > jax.device_count():
            raise SystemExit(
                f"weakscale: {nd} devices requested, "
                f"{jax.device_count()} available (set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={nd})")
        for family, engine in FAMILY_ENGINES.items():
            n = base_n * nd
            spec = RunSpec(
                lattice=LatticeSpec(n=n, m=cols),
                engine=EngineSpec(engine), temperature=2.27, seed=3,
                mesh=MeshSpec(shape=(nd, 1),
                              axis_names=("rows", "cols")))
            session = Session.open(spec)
            session.run(sweeps)            # warmup: compile + place
            session.magnetization()
            hx0 = tel.HALO_EXCHANGES.value
            hb0 = tel.HALO_BYTES.value
            times = []
            for _ in range(trials):
                t0 = time.perf_counter()
                session.run(sweeps)
                session.magnetization()    # host sync
                times.append(time.perf_counter() - t0)
            hx = (tel.HALO_EXCHANGES.value - hx0) / trials
            hb = (tel.HALO_BYTES.value - hb0) / trials
            attrs = session._runner._dist_attrs
            reps = ENGINES[engine].replicas
            dt = sum(times) / len(times)
            rows.append({
                "name": f"dist_{family}_d{nd}",
                "us": dt * 1e6,
                "times_s": times,
                "engine": engine,
                "k": int(attrs.get("halo_k", 1)),
                "spec": spec.to_json(),
                "derived": {
                    "flips_per_ns": reps * n * cols * sweeps / dt / 1e9,
                    "devices": nd,
                    "sweeps": sweeps,
                    "sharded_resident":
                        int(attrs.get("sharded_resident", False)),
                    "halo_k": int(attrs.get("halo_k", 1)),
                    "halo_exchanges_per_call": hx,
                    "halo_kb_per_call": round(hb / 1024, 3),
                },
            })
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.dist.weakscale",
        description="weak-scaling bench of the sharded resident tier")
    ap.add_argument("--devices", default="1,2,4,8",
                    help="comma list of device counts (each a (D,1) "
                         "mesh over the first D devices)")
    ap.add_argument("--base-n", type=int, default=64,
                    help="lattice rows PER DEVICE (n = base_n * D)")
    ap.add_argument("--cols", type=int, default=128)
    ap.add_argument("--sweeps", type=int, default=4,
                    help="sweeps per timed call")
    ap.add_argument("--trials", type=int, default=2)
    ap.add_argument("--json", nargs="?", const=".", default=None,
                    metavar="DIR_OR_PATH",
                    help="write a BENCH_<stamp>.json perf record "
                         "(marked filtered: meta.only = 'dist')")
    args = ap.parse_args(argv)
    devices = [int(d) for d in args.devices.split(",") if d]
    if not devices or any(d < 1 for d in devices):
        ap.error(f"--devices must be positive ints, got {args.devices!r}")
    # before JAX's backend starts: on a CPU host the weak-scaling axis
    # needs forced host devices (the flag is inert on accelerators)
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")

    import jax
    from repro.analysis.recorder import RunRecorder
    from repro.launch import roofline as rl
    stamp = time.strftime("%Y%m%d_%H%M%S")
    rec = RunRecorder(echo=True, meta={
        "stamp": stamp, "backend": jax.default_backend(),
        "device_count": jax.device_count(), "only": "dist",
        "trials": args.trials})
    for row in measure_rows(devices, base_n=args.base_n,
                            cols=args.cols, sweeps=args.sweeps,
                            trials=args.trials):
        derived = dict(row["derived"])
        derived["engine"] = row["engine"]
        pct = rl.pct_of_roofline(derived["flips_per_ns"],
                                 row["engine"], jax.default_backend(),
                                 k=row["k"])
        if pct is not None:
            derived["pct_of_roofline"] = round(pct, 4)
        rec.record(row["name"], row["us"], spec=row["spec"],
                   times_us=[t * 1e6 for t in row["times_s"]],
                   **derived)
    if args.json is not None:
        from repro.perf.schema import validate_record
        validate_record({"meta": rec.meta, "rows": rec.rows})
        print(f"# wrote {rec.write_json(args.json)}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
