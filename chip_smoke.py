#!/usr/bin/env python3
"""Smoke run of the main path on TPU chips: RunSpec -> Session -> Pallas.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # multispin on a 2x2 mesh

One chip: each Pallas engine (stencil, multispin, bitplane) runs from an
ordered start at T = 2.0 through ``Session.run`` and one
``Session.measure`` block, twice:

* at chip size on the per-half-sweep tier -- 32768^2 stencil (2^30 spins,
  1 GiB of int8 planes), 32768^2 multispin (512 MiB of words) and
  8192^2 x 32 replicas bitplane (256 MiB of words);
* at the largest lattice the VMEM planner admits to the resident tier.

Each run must match, bit for bit (``Session.state_digest``), its jnp
oracle engine (``basic_philox``, ``multispin``, ``bitplane``) run on the
chip with the same spec; put |m| near Onsager's value; lower to a
program holding a ``tpu_custom_call``; and leave the ``resident.demote``
and ``resilience.retry`` counters at 0.

Four chips: ``multispin_pallas`` on a 2x2 ``MeshSpec`` at 32768^2
(16384^2 shards, the per-half-sweep sharded path) and at 1024^2, whose
shards fit the sharded resident tier (``repro.dist``); each digest must
equal that of the same spec on one device, and each device's memory in
use is printed (all shards on device 0 would show there).

Every line but the last is a human-readable report; timings in it are
smoke timings (first call includes compilation), not benchmarks.  The
last line is one JSON object: ``{"ok": true, "device": {...}}``.  Any
failed check raises, so the script then exits non-zero without that
line.  Without a TPU it exits 2 before running anything.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

#: (engine, its jnp oracle, chip-size lattice side)
ENGINES = (("stencil_pallas", "basic_philox", 32768),
           ("multispin_pallas", "multispin", 32768),
           ("bitplane_pallas", "bitplane", 8192))
TEMPERATURE = 2.0
SEED = 20260
SWEEPS = 4            # per Session.run call; two calls per run
MEASURE = (2, 2)      # Session.measure block: n_measure, sweeps_between
M_TOL = 0.02          # |m| against Onsager's spontaneous magnetization
#: four-chip lattice sides, and whether their shards run resident
FOUR_CHIP_RUNS = ((32768, False), (1024, True))


def log(msg: str) -> None:
    print(msg, flush=True)


def _state_bytes(session) -> int:
    import jax
    return sum(a.nbytes for a in jax.tree_util.tree_leaves(session.state))


def _lowered_has_kernel(session) -> bool:
    """Lower one sweep of the session's engine and look for Mosaic."""
    import jax
    import jax.numpy as jnp
    eng = session.engine
    fn = jax.jit(lambda s: eng.sweep_fn(
        s, jnp.float32(eng.cfg.inv_temp), eng.cfg.seed, 0, 1))
    return "tpu_custom_call" in fn.lower(session.state).as_text()


def _drive(spec, measure=True):
    """Open ``spec``, run it twice and (unless told not to) measure once;
    return the session, the trajectory and the seconds of each call."""
    from repro.analysis.measure import MeasurementPlan
    from repro.api import Session
    session = Session.open(spec)
    times = []
    for _ in range(2):
        t = time.perf_counter()
        session.run(SWEEPS)
        session.magnetization()          # waits for the device
        times.append(time.perf_counter() - t)
    traj = {}
    if measure:
        t = time.perf_counter()
        traj = session.measure(MeasurementPlan(*MEASURE))
        times.append(time.perf_counter() - t)
    return session, traj, times


def _release() -> str:
    """Collect dropped sessions; report the chip's bytes in use."""
    import gc

    import jax
    gc.collect()
    in_use = (jax.devices()[0].memory_stats() or {}).get("bytes_in_use")
    return f"{in_use} B in use after release"


def _abs_m(traj) -> float:
    import numpy as np
    return float(np.mean(np.abs(traj["m"][-1])))


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def _counters_zero(label: str) -> None:
    import repro.telemetry as tel
    for name in ("resident.demote", "resilience.retry"):
        value = tel.REGISTRY.counter(name).value
        _check(value == 0, f"{label}: counter {name} = {value}, want 0")


def one_chip(device) -> None:
    import dataclasses

    from repro.api import EngineSpec, LatticeSpec, RunSpec
    from repro.core.observables import onsager_magnetization
    from repro.kernels import resident

    m_ref = float(onsager_magnetization(TEMPERATURE))
    hbm = (device.memory_stats() or {}).get("bytes_limit") or 0
    family = {"stencil_pallas": "stencil", "multispin_pallas": "multispin",
              "bitplane_pallas": "bitplane"}
    for engine, oracle, chip_n in ENGINES:
        res_n = resident.max_square_lattice(family[engine])
        for n, tier in ((chip_n, "per-half-sweep"), (res_n, "resident")):
            label = f"{engine} {n}x{n} {tier}"
            spec = RunSpec(lattice=LatticeSpec(n=n, m=n, init_p_up=1.0),
                           engine=EngineSpec(engine),
                           temperature=TEMPERATURE, seed=SEED)
            session, traj, (t_first, t_steady, t_meas) = _drive(spec)
            got = "resident" if session.engine.resident_plan else \
                "per-half-sweep"
            _check(got == tier, f"{label}: engine chose the {got} tier")
            _check(_lowered_has_kernel(session),
                   f"{label}: lowered sweep holds no tpu_custom_call")
            digest = session.state_digest()
            nbytes = _state_bytes(session)
            abs_m = _abs_m(traj)
            del session
            _release()
            oracle_spec = dataclasses.replace(spec,
                                              engine=EngineSpec(oracle))
            ref, ref_traj, _ = _drive(oracle_spec)
            ref_digest = ref.state_digest()
            del ref
            freed = _release()
            _check(digest == ref_digest,
                   f"{label}: digest {digest} != {oracle} {ref_digest}")
            _check(all((traj[f] == ref_traj[f]).all() for f in traj),
                   f"{label}: measured trajectory differs from {oracle}")
            _check(abs(abs_m - m_ref) < M_TOL,
                   f"{label}: |m| = {abs_m}, Onsager {m_ref}")
            _counters_zero(label)
            share = f"{nbytes / hbm:.4f}" if hbm else "unknown"
            log(f"{label}: state {nbytes} B = {share} of HBM; digest "
                f"{digest} == {oracle}; |m| {abs_m:.4f} (Onsager "
                f"{m_ref:.4f}); smoke timings: first run({SWEEPS}) "
                f"{t_first:.2f} s incl. compile, steady run({SWEEPS}) "
                f"{t_steady:.2f} s, measure{MEASURE} {t_meas:.2f} s; "
                f"{freed}")


def four_chips(devices) -> None:
    import dataclasses

    from repro.api import EngineSpec, LatticeSpec, MeshSpec, RunSpec

    mesh = MeshSpec(shape=(2, 2), axis_names=("rows", "cols"))
    for n, want_resident in FOUR_CHIP_RUNS:
        spec = RunSpec(lattice=LatticeSpec(n=n, m=n, init_p_up=1.0),
                       engine=EngineSpec("multispin_pallas"),
                       temperature=TEMPERATURE, seed=SEED, mesh=mesh)
        session, _, (t_first, t_steady) = _drive(spec, measure=False)
        attrs = session._runner._dist_attrs
        tier = "sharded resident" if attrs.get("sharded_resident") else \
            "per-half-sweep sharded"
        label = f"multispin_pallas {n}x{n} on 2x2 ({tier})"
        _check(bool(attrs.get("sharded_resident")) == want_resident,
               f"{label}: want sharded_resident={want_resident}")
        digest = session.state_digest()
        in_use = [(d.memory_stats() or {}).get("bytes_in_use")
                  for d in devices]
        del session
        _release()
        single, _, _ = _drive(dataclasses.replace(spec, mesh=None),
                              measure=False)
        single_digest = single.state_digest()
        del single
        _release()
        _check(digest == single_digest,
               f"{label}: digest {digest} != one device {single_digest}")
        _counters_zero(label)
        log(f"{label}: {attrs}; digest {digest} == one device; "
            f"bytes_in_use per device after the sharded run {in_use}; "
            f"smoke timings: first run({SWEEPS}) {t_first:.2f} s incl. "
            f"compile, steady run({SWEEPS}) {t_steady:.2f} s")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only multispin on a 2x2 mesh vs one device")
    args = ap.parse_args(argv)

    from repro import compile_cache
    log(f"compile cache: {compile_cache.enable()}")
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform "
              f"{devices[0].platform!r}); nothing was run",
              file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: {need} chips needed, {len(devices)} found",
              file=sys.stderr)
        return 2
    log(f"device: {devices[0].device_kind} x {len(devices)}")
    if args.four_chips:
        four_chips(devices[:4])
    else:
        one_chip(devices[0])
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
