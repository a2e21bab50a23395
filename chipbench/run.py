#!/usr/bin/env python3
"""Chip benchmark of the 2D Ising engines, driven through ``Session``.

    python chipbench/run.py --workload multispin.sweep --seed 7 \\
        --seconds 10 --trace 0

One process runs one cell once.  A cell (a ``workloads`` entry of
``BENCHMARK.json``) names a configuration and a traffic; the harness
finds each by name and holds no cell of its own:

* ``chipbench/configs/<config>.json``  -- the deployment: engine, lattice,
  temperature, initial state, mesh, and the names of its state layout
  and random stream;
* ``chipbench/layouts/<layout>.py``    -- how the engine's state arrays
  hold lattices: ``LATTICES`` (how many an array holds), ``hot_start(key,
  n, m)``, ``plane(a, r)`` (lattice r as the reference's int8 +-1 plane),
  ``put(a, r, plane)`` (its inverse) and ``count_differ(plane, a, r)``;
* ``chipbench/streams/<stream>.py``    -- the random stream the reference
  replays: ``flips(t, nn, rows, beta, k0, k1, offset, precision)``;
* ``chipbench/traffic/<traffic>.json`` -- the run plan: ``sweep``
  (``Session.run(k)`` repeated) or ``measure`` (``Session.measure(plan)``
  repeated), with the limits of what its check compares;
* ``chipbench/metrics/<metric>.py``    -- one reader per per-layer metric,
  ``read(ctx) -> float | None``;
* ``chipbench/peaks.json``             -- peaks keyed by ``device_kind``.

Set-up (``setup_s``: process start to the first timed call) draws a hot
start on the device from ``--seed`` in one jitted call, opens a
``Session`` on it, and runs the traffic's call twice so that every program is compiled or loaded
from the persistent cache.  The window then repeats the call until
``--seconds`` have passed and blocks on the state; ``flips_per_ns`` is
n * m * sweeps * lattices over the whole window.  One call, drawn from
the seed, is bracketed by copies of the state; once the window has
closed and the session is freed, ``chipbench/reference.py`` replays that
call for every lattice the state holds, and the spins (and, for
``measure``, the observables of each lattice) are compared.  A compile
inside the window fails the run.  ``--trace 1`` runs the same window
under the profiler and reports the per-layer metrics instead.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, ``breakdown``,
``checks``); the last lines of standard error give each compared number
beside its limit.  Without a TPU, with fewer chips than the cell asks
for, or on a device missing from ``peaks.json``, the run exits non-zero
and prints no result.
"""
from __future__ import annotations

import time

_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

#: JAX events that mean something was traced, lowered or compiled
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
#: calls of the traffic made in set-up, before the window
WARM_CALLS = 2
#: int8 cells the reference replays at once: a 65536^2 lattice, the
#: largest one-chip cell's; a state of more lattices is replayed in groups
REPLAY_CELLS = 2 ** 32
#: what a layout and a stream file define
LAYOUT_NAMES = ("LATTICES", "hot_start", "plane", "put", "count_differ")
STREAM_NAMES = ("flips",)


class CellError(Exception):
    """A cell, or a file it names, is missing or malformed."""


class NoChip(Exception):
    """No accelerator, too few chips, or a device without peaks."""


def _load_json(path: Path, what: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise CellError(f"{what}: no file {path}") from None


def _plugin(root: Path, kind: str, name: str, what: str) -> Path:
    """The file of ``chipbench/<kind>/`` found by ``name``."""
    path = root / "chipbench" / kind / f"{name}.py"
    if not path.is_file():
        raise CellError(f"{what} {name!r}: no file {path}")
    return path


def _load_plugin(path: Path, what: str, names):
    """The module at ``path``; it has to define every one of ``names``."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + "_".join(path.parts[-2:]).replace(".", "_")
        .replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    missing = [n for n in names if not hasattr(mod, n)]
    if missing:
        raise CellError(f"{what}: {path} has no {', '.join(missing)}")
    return mod


def _load_reader(path: Path, name: str):
    return _load_plugin(path, f"per-layer metric {name!r}", ("read",)).read


def load_cell(name: str, root: Path = ROOT) -> dict:
    """The cell ``name`` of ``root/BENCHMARK.json`` with every file it
    names loaded: config, traffic, the config's state layout and random
    stream, and a reader per per-layer metric."""
    bench = _load_json(root / "BENCHMARK.json", "benchmark")
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise CellError(f"workload {name!r} not in BENCHMARK.json; have "
                        f"{sorted(work)}")
    w = work[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise CellError(f"workload {name!r}: config {w['config']!r} not "
                        "in BENCHMARK.json")
    config = _load_json(root / configs[w["config"]]["file"],
                        f"config {w['config']!r}")
    traffic = _load_json(root / "chipbench" / "traffic"
                         / f"{w['traffic']}.json", f"traffic "
                         f"{w['traffic']!r}")
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    # every file is found before any is loaded
    layout = _plugin(root, "layouts", config["layout"], "state layout")
    stream = _plugin(root, "streams", config["stream"], "random stream")
    readers = {m["name"]: _plugin(root, "metrics", m["name"],
                                  "per-layer metric") for m in per_layer}
    return {"name": name, "workload": w, "config": config,
            "traffic": traffic, "end_to_end": e2e, "per_layer": per_layer,
            "readers": {k: _load_reader(p, k) for k, p in readers.items()},
            "layout": _load_plugin(layout, "state layout", LAYOUT_NAMES),
            "stream": _load_plugin(stream, "random stream", STREAM_NAMES),
            "peaks": _load_json(root / "chipbench" / "peaks.json",
                                "peaks")}


# -- the program under test --------------------------------------------------

def _spec(config: dict, seed: int):
    from repro.api import EngineSpec, LatticeSpec, MeshSpec, RunSpec
    mesh = config.get("mesh")
    return RunSpec(
        lattice=LatticeSpec(n=config["n"], m=config["m"],
                            init_p_up=config["init_p_up"]),
        engine=EngineSpec(config["engine"]),
        temperature=config["temperature"], seed=seed,
        mesh=None if mesh is None else MeshSpec(
            shape=tuple(mesh["shape"]),
            axis_names=tuple(mesh["axis_names"])))


def _hot_start(config: dict, layout, seed: int):
    """The lattices of a hot start, drawn on the default device from the
    seed in one jitted call, as the named state arrays of the config's
    engine: each spin +1 or -1 with probability 1/2."""
    import jax
    if config["init_p_up"] != 0.5:
        raise CellError(f"config {config['name']!r}: only a hot start "
                        "(init_p_up 0.5) is drawn")
    n, m = config["n"], config["m"]

    @jax.jit
    def make(key):
        return [layout.hot_start(k, n, m) for k in jax.random.split(key)]

    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                             (seed >> 32) & 0x7FFFFFFF)
    return dict(zip(config["arrays"], make(key)))


def _open(cell: dict, seed: int):
    """A ``Session`` at sweep 0 on the hot start: the runner and engine
    are built as ``Session.restore`` builds them, with the arrays handed
    over on the device instead of read from a file."""
    from repro.api import Session
    config = cell["config"]
    return Session._from_arrays(_spec(config, seed),
                                _hot_start(config, cell["layout"], seed), 0)


def _call(session, traffic: dict):
    """One call of the traffic; returns the measured samples or None."""
    if traffic["kind"] == "sweep":
        session.run(traffic["sweeps_per_call"])
        return None
    if traffic["kind"] == "measure":
        from repro.analysis.measure import MeasurementPlan
        return session.measure(MeasurementPlan(
            n_measure=traffic["n_measure"],
            sweeps_between=traffic["sweeps_between"],
            fields=tuple(traffic["fields"])))
    raise CellError(f"traffic kind {traffic['kind']!r} is neither "
                    "'sweep' nor 'measure'")


def sweeps_per_call(traffic: dict) -> int:
    if traffic["kind"] == "measure":
        return traffic["n_measure"] * traffic["sweeps_between"]
    return traffic["sweeps_per_call"]


@contextlib.contextmanager
def _compiles():
    """Yields a counter of JAX's trace, lowering and compile events,
    counting while its ``on`` is true."""
    import jax

    class Count:
        on = False
        n = 0

        def __call__(self, event, duration, **kwargs):
            if self.on and event in COMPILE_EVENTS:
                self.n += 1

    count = Count()
    jax.monitoring.register_event_duration_secs_listener(count)
    try:
        yield count
    finally:
        jax.monitoring.unregister_event_duration_listener(count)


def _copy(state):
    import jax
    import jax.numpy as jnp
    return jax.tree.map(lambda a: jnp.array(a, copy=True), state)


def _devices(chips: int, require_chip: bool, peaks: dict):
    import jax
    devs = jax.devices()
    if require_chip:
        if devs[0].platform != "tpu":
            raise NoChip(f"no TPU: JAX platform is {devs[0].platform!r}")
        if len(devs) < chips:
            raise NoChip(f"cell needs {chips} chips, JAX finds "
                         f"{len(devs)}")
        if devs[0].device_kind not in peaks["devices"]:
            raise NoChip(f"device kind {devs[0].device_kind!r} has no row "
                         "in chipbench/peaks.json")
    return devs


def run_cell(cell: dict, seed: int, seconds: float, trace: bool = False,
             *, require_chip: bool = True, control: bool = False,
             trace_dir=None, log=print) -> dict:
    """Run one cell once; returns the result line as a dict.

    ``control`` puts the reference, in bfloat16, in the program's place
    for the checked call (its comparison has to fail).  ``trace_dir``
    keeps the raw trace and its events there."""
    devs = _devices(cell["workload"]["chips"], require_chip, cell["peaks"])
    with _compiles() as compiles:
        return _run(cell, seed, seconds, trace, control, trace_dir, log,
                    devs, compiles)


def _run(cell, seed, seconds, trace, control, trace_dir, log, devs,
         compiles):
    import jax

    import repro.telemetry as tel

    used = devs[:cell["workload"]["chips"]]
    config, traffic = cell["config"], cell["traffic"]
    k = sweeps_per_call(traffic)
    t_open = time.perf_counter()
    session = _open(cell, seed)
    jax.block_until_ready(session.state)
    t_warm = time.perf_counter()
    for _ in range(WARM_CALLS):
        _call(session, traffic)
    probe = jax.jit(lambda a: a[:1, :1])
    jax.block_until_ready((probe(session.state[0]), _copy(session.state)))
    log(f"set-up: start {t_open - _PROCESS_START:.1f} s, hot start "
        f"{t_warm - t_open:.1f} s, {WARM_CALLS} warm calls "
        f"{time.perf_counter() - t_warm:.1f} s")
    # the call checked against the reference starts after this share of
    # the window (or is the one after the window's end, if none did)
    frac = float(np.random.default_rng(seed).uniform(0.0, 0.8))

    tmp = None
    if trace:
        tmp = tempfile.mkdtemp(prefix="chipbench-trace-")
        jax.profiler.start_trace(tmp)
    d0 = tel.DISPATCHES.value
    compiles.on = True
    calls, checked, samples, snaps = 0, None, None, {}
    pending = None
    t_first = time.perf_counter()
    with jax.profiler.TraceAnnotation("chipbench.window"):
        while True:
            now = time.perf_counter() - t_first
            if now >= seconds and checked is not None:
                break
            if checked is None and now >= frac * seconds:
                with jax.profiler.TraceAnnotation("chipbench.snapshot"):
                    snaps["before"] = _copy(session.state)
                checked = calls
            with jax.profiler.TraceAnnotation(
                    f"chipbench.{traffic['kind']}"):
                out = _call(session, traffic)
            if checked == calls:
                with jax.profiler.TraceAnnotation("chipbench.snapshot"):
                    snaps["after"] = _copy(session.state)
                samples = out
            marker = probe(session.state[0])
            if pending is not None:
                with jax.profiler.TraceAnnotation("chipbench.wait"):
                    pending.block_until_ready()
            pending = marker
            calls += 1
        with jax.profiler.TraceAnnotation("chipbench.wait"):
            jax.block_until_ready(session.state)
    t_end = time.perf_counter()
    compiles.on = False
    dispatches = tel.DISPATCHES.value - d0
    reduced = None
    if trace:
        jax.profiler.stop_trace()
        try:
            reduced = _reduce_trace(tmp, trace_dir)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)

    elapsed = t_end - t_first
    sweeps = calls * k
    flips = sweeps * config["n"] * config["m"] * cell["layout"].LATTICES
    peak_mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in used)
    step0 = (WARM_CALLS + checked) * k
    del session
    gc.collect()

    t_ref = time.perf_counter()
    checks = _checks(cell, seed, step0, snaps, samples, control, used[0])
    checks["compiles_in_window"] = {"value": compiles.n, "limit": 0}
    log(f"reference replay of call {checked} (sweeps {step0}..{step0 + k}) "
        f"took {time.perf_counter() - t_ref:.1f} s")
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak_mem)}
    result = {"correct": correct, "attempted": calls,
              "failed": 0 if correct else 1}
    if trace:
        ctx = {"workload": cell["name"], "config": config,
               "traffic": traffic, "flips": flips, "sweeps": sweeps,
               "calls": calls, "window_s": elapsed, "trace": reduced,
               "dispatches": dispatches, "chips": len(used),
               "peak": cell["peaks"]["devices"].get(devs[0].device_kind)}
        metrics = {}
        for m in cell["per_layer"]:
            v = cell["readers"][m["name"]](ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        busy = [d["busy_ns"] for d in reduced["devices"].values()]
        device["busy_s"] = sum(busy) / max(len(busy), 1) / 1e9
        device["window_s"] = reduced["window_ns"] / 1e9
        result["metrics"] = metrics
        result["device"] = device
        from chipbench import trace as ctrace
        result["breakdown"] = ctrace.breakdown(reduced)
    else:
        values = {"flips_per_ns": flips / (elapsed * 1e9),
                  "setup_s": t_first - _PROCESS_START}
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell["end_to_end"]}
        result["device"] = device
    result["checks"] = checks
    return result


def _reduce_trace(tmp: str, keep) -> dict:
    from chipbench import trace as ctrace
    found = sorted(Path(tmp).rglob("*.xplane.pb"))
    if len(found) != 1:
        raise RuntimeError(f"profiler wrote {len(found)} .xplane.pb files")
    if keep:
        os.makedirs(keep, exist_ok=True)
        shutil.copy(found[0], Path(keep) / found[0].name)
    ev = ctrace.events(str(found[0]))
    if keep:
        with open(Path(keep) / "events.json", "w") as f:
            json.dump(ev, f)
    return ctrace.reduce(ev)


def _groups(lattices: int, cells: int):
    """The lattice indices the reference replays together, at most
    ``REPLAY_CELLS`` int8 cells at a time (``cells`` to a lattice)."""
    per = max(1, min(lattices, REPLAY_CELLS // cells))
    return [range(r, min(r + per, lattices))
            for r in range(0, lattices, per)]


def _gap(got, want) -> float:
    """Largest |got - want| over samples and lattices; ``got`` is the
    program's (samples,) or (samples, lattices) array, ``want`` the
    reference's (samples, lattices).  inf where the shapes disagree."""
    got = np.asarray(got, np.float64)
    if got.ndim not in (1, 2) or got.shape[0] != want.shape[0] \
            or got.size != want.size:
        return float("inf")
    return max(abs(float(g) - float(w)) for g, w in
               zip(got.reshape(want.shape).flat, want.flat))


def _checks(cell, seed, step0, snaps, samples, control, device) -> dict:
    """Replay the checked call with the reference, every lattice the
    state holds; every compared number with its limit.  ``snaps`` holds
    the state copies taken ``before`` and ``after`` the call; each is
    dropped once used, so that the replay of a large lattice fits beside
    them."""
    import jax

    from chipbench import reference as ref
    config, traffic, layout = cell["config"], cell["traffic"], cell["layout"]
    every = traffic["sweeps_between"] if traffic["kind"] == "measure" \
        else 0
    kw = dict(temperature=config["temperature"], seed=seed, step0=step0,
              n_sweeps=sweeps_per_call(traffic), stream=cell["stream"],
              observe_every=every)
    n = config["n"] * config["m"]
    before = [jax.device_put(a, device) for a in snaps.pop("before")]
    if control:
        # the reference in bfloat16 stands in for the program's call,
        # its result held in the program's layout
        del snaps["after"]
        got, csamp = before, []
    else:
        got = [jax.device_put(a, device) for a in snaps.pop("after")]
    differ, rsamp = 0, []
    groups = _groups(layout.LATTICES, n)
    for group in groups:
        b0, w0 = ([layout.plane(a, r) for r in group] for a in before)
        if group is groups[-1]:
            del before
        if control:
            cb, cw, cs = ref.sweeps(b0, w0, precision="bfloat16", **kw)
            for i, r in enumerate(group):
                got = [layout.put(got[0], r, cb[i]),
                       layout.put(got[1], r, cw[i])]
            del cb, cw
            csamp.append(cs)
        rb, rw, rs = ref.sweeps(b0, w0, **kw)
        del b0, w0
        differ += sum(int(layout.count_differ(rb[i], got[0], r))
                      + int(layout.count_differ(rw[i], got[1], r))
                      for i, r in enumerate(group))
        del rb, rw
        rsamp.append(rs)
    checks = {"spins_differ": {"value": differ, "limit": 0}}
    if every:
        # (samples, lattices) arrays, the groups side by side
        def per_lattice(parts, which):
            return np.array([[obs[which] for g in sample for obs in g]
                             for sample in zip(*parts)], dtype=object)

        big_m, big_b = per_lattice(rsamp, 0), per_lattice(rsamp, 1)
        if control:
            samples = {"m": np.float32(per_lattice(csamp, 0) / n),
                       "e": np.float32(-per_lattice(csamp, 1) / n)}
        limits = traffic["limits"]
        checks["m_gap"] = {"value": _gap(samples["m"], big_m / n),
                           "limit": limits["m_gap"]}
        checks["e_gap"] = {"value": _gap(samples["e"], -big_b / n),
                           "limit": limits["e_gap"]}
    return checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="put the bfloat16 reference in the program's "
                    "place for the checked call (its check must fail)")
    ap.add_argument("--trace-dir", default=None,
                    help="keep the raw trace and its events here")
    args = ap.parse_args(argv)

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    # the layout and stream files import chipbench.reference
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    try:
        cell = load_cell(args.workload)
    except CellError as e:
        log(f"chipbench: {e}")
        return 2
    try:
        from repro import compile_cache
    except ImportError as e:
        log(f"chipbench: the program is not here ({e})")
        return 2
    import jax
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          control=args.control, trace_dir=args.trace_dir,
                          log=log)
    except NoChip as e:
        log(f"chipbench: {e}; nothing was measured")
        return 3
    for name, c in result["checks"].items():
        log(f"check {name} = {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
