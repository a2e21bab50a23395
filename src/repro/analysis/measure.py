"""Fused on-device measurement: observables inside one compiled scan.

The legacy measurement path (``Simulation.trajectory``) is a Python loop
that issues one device dispatch per sample and round-trips every
observable to the host; the TPU-cluster follow-up to the paper (Yang et
al.) shows the measurement loop must be fused into the compiled update to
stay accelerator-bound.  :func:`measure_scan` is that fusion: a
``MeasurementPlan`` (how many samples, spaced how far apart) is compiled
into ONE ``jax.lax.scan`` whose body advances the engine by
``sweeps_between`` sweeps via the pure ``Engine.scan_step`` hook and
records ``Engine.observables`` -- one dispatch per trajectory segment
instead of one per sample, with bit-identical samples (DESIGN.md S7).

Two entry points share the compiled body:

* :func:`measure_scan`          -- single simulation; the seed is closed
  over as a python int (full 64-bit Philox keys, exactly like the
  stateful ``sweeps`` wrappers);
* :func:`measure_scan_batched`  -- ``vmap`` over (state, inv_temp, seed)
  for the :class:`~repro.core.ensemble.Ensemble` driver (counter-based
  engines only, traced uint32 seeds).

Each compiled-call invocation is accounted through ``repro.telemetry``
(one ``dispatches`` increment + a fenced ``measure_scan``/``dispatch``
span when tracing is on); tests and the fusion bench read the counter
to assert the one-dispatch contract.  The old module global
``DISPATCH_COUNT`` survives as a deprecated read-only alias of the
telemetry counter.

Resident-tier composition (DESIGN.md S9): the scan body advances each
measure interval through ``Engine.scan_step`` -> ``sweep_fn``, so on a
resident-capable engine whose lattice fits the VMEM plan every
``sweeps_between``-sized sweep block lowers to exactly ONE k-sweep
resident kernel call (k = ``sweeps_between``) inside the scan -- the
spins stay in VMEM for the whole interval and touch HBM once per
sample, instead of 2x per sweep.  No code here knows about the tier;
the mapping falls out of the registry dispatch, and bit-exactness of
the samples is guaranteed by the shared Philox counter layout
(``core.rng.half_sweep_offset``, tested in tests/test_resident.py).
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

import repro.telemetry as tel


def __getattr__(name: str):
    # deprecation shim (PEP 562): the pre-telemetry mutable global is now
    # a read-only view of the process-global dispatch counter
    if name == "DISPATCH_COUNT":
        import warnings
        warnings.warn(
            "repro.analysis.measure.DISPATCH_COUNT is deprecated; read "
            "repro.telemetry.DISPATCHES.value (or snapshot counter "
            "deltas) instead", DeprecationWarning, stacklevel=2)
        return tel.DISPATCHES.value
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


@dataclasses.dataclass(frozen=True)
class MeasurementPlan:
    """A measurement schedule: ``n_measure`` samples, ``sweeps_between``
    sweeps apart, after ``thermalize`` equilibration sweeps.

    ``fields`` selects which keys of the engine ``observables`` hook are
    recorded ("m" mean spin, "e" energy per spin).  Frozen + hashable:
    the plan is the jit-cache key.
    """

    n_measure: int
    sweeps_between: int
    thermalize: int = 0
    fields: Tuple[str, ...] = ("m", "e")

    def __post_init__(self):
        assert self.n_measure > 0 and self.sweeps_between > 0, self
        assert self.thermalize >= 0, self
        assert len(self.fields) > 0, "need at least one observable field"
        object.__setattr__(self, "fields", tuple(self.fields))

    @property
    def total_sweeps(self) -> int:
        return self.thermalize + self.n_measure * self.sweeps_between


def _scan_body(engine, plan: MeasurementPlan):
    """The traced trajectory: thermalize, then scan measure intervals."""

    def run(state, inv_temp, seed, step0):
        if plan.thermalize:
            state = engine.scan_step(state, inv_temp, seed, step0,
                                     plan.thermalize)
            step0 = step0 + plan.thermalize

        def body(carry, _):
            st, step = carry
            st = engine.scan_step(st, inv_temp, seed, step,
                                  plan.sweeps_between)
            step = step + plan.sweeps_between
            with jax.named_scope("observables"):
                o = engine.observables(st, inv_temp)
            missing = set(plan.fields) - set(o)
            if missing:
                raise ValueError(
                    f"plan fields {sorted(missing)} not in engine "
                    f"{engine.name!r} observables {sorted(o)}")
            sample = {k: jnp.asarray(o[k], jnp.float32)
                      for k in plan.fields}
            return (st, step), sample

        (state, _), traj = jax.lax.scan(body, (state, step0), None,
                                        length=plan.n_measure)
        return state, traj

    return run


def _compiled(engine, plan: MeasurementPlan, batched: bool):
    """Returns ``(fn, fresh)``: ``fresh`` marks a cache miss, i.e. the
    next invocation pays XLA compilation (the ``compile`` span attr)."""
    # cache lives on the engine instance (the CounterEngine._jit_cache
    # pattern) so compiled executables die with the engine
    cache = engine.__dict__.setdefault("_measure_scan_cache", {})
    fn = cache.get((plan, batched))
    fresh = fn is None
    if fn is None:
        run = _scan_body(engine, plan)
        if batched:
            # (states, inv_temps, seeds) carry the batch axis; the sweep
            # counter is shared -- every member is at the same step
            fn = jax.jit(jax.vmap(run, in_axes=(0, 0, 0, None)))
        else:
            # close the python-int seed over the trace so counter-based
            # engines keep full 64-bit Philox keys (same convention as
            # the stateful CounterEngine.sweeps wrapper)
            seed = engine.cfg.seed
            fn = jax.jit(lambda st, beta, step0: run(st, beta, seed,
                                                     step0))
        cache[(plan, batched)] = fn
    return fn, fresh


def _span(engine, plan: MeasurementPlan, fresh: bool, batch: int):
    return tel.span("measure_scan", engine=engine.name,
                    lattice=(engine.cfg.n, engine.cfg.m),
                    n_measure=plan.n_measure,
                    sweeps_between=plan.sweeps_between,
                    thermalize=plan.thermalize, batch=batch,
                    replicas=engine.replicas,
                    observables=engine.observables_path,
                    compile="first" if fresh else "steady")


def _account(engine, plan: MeasurementPlan, batch: int) -> None:
    tel.record_dispatch(n_sweeps=plan.total_sweeps,
                        sites=engine.cfg.n * engine.cfg.m,
                        replicas=engine.replicas, batch=batch,
                        counter_based=engine.counter_based)


def measure_scan(engine, state, plan: MeasurementPlan, step_count: int = 0):
    """Run ``plan`` on a single simulation state in one compiled dispatch.

    Returns ``(final_state, {field: (n_measure,) float32 ndarray},
    new_step_count)``.  Replicated engines (bitplane) append their
    per-replica axis: ``(n_measure, replicas)``.  Samples are
    bit-identical to the legacy python loop ``run(sweeps_between);
    measure()`` repeated ``n_measure`` times (tests/test_analysis.py).
    """
    fn, fresh = _compiled(engine, plan, batched=False)
    with _span(engine, plan, fresh, batch=1) as sp:
        with tel.span("dispatch", engine=engine.name,
                      k=plan.total_sweeps,
                      compile="first" if fresh else "steady") as dsp:
            state, traj = fn(state, jnp.float32(engine.cfg.inv_temp),
                             jnp.int32(step_count))
            dsp.fence(traj)
        _account(engine, plan, batch=1)
        sp.fence((state, traj))
    with tel.span("measure.fetch", engine=engine.name,
                  n_measure=plan.n_measure):
        traj = {k: np.asarray(v) for k, v in traj.items()}
    return state, traj, step_count + plan.total_sweeps


def measure_scan_batched(engine, states, inv_temps, seeds,
                         plan: MeasurementPlan, step_count: int = 0):
    """Batched :func:`measure_scan` over (state, inv_temp, seed) members.

    Returns ``(final_states, {field: (n_measure, B) ndarray},
    new_step_count)`` -- trajectory-major, matching the legacy
    ``Ensemble.trajectory`` shape.
    """
    if not engine.counter_based:
        raise ValueError(
            f"engine {engine.name!r} is not counter-based; batched "
            "measurement needs a traceable-seed sweep (DESIGN.md S3/S4)")
    batch = int(np.shape(seeds)[0])
    fn, fresh = _compiled(engine, plan, batched=True)
    with _span(engine, plan, fresh, batch=batch) as sp:
        with tel.span("dispatch", engine=engine.name,
                      k=plan.total_sweeps, batch=batch,
                      compile="first" if fresh else "steady") as dsp:
            states, traj = fn(states, inv_temps, seeds,
                              jnp.int32(step_count))
            dsp.fence(traj)
        _account(engine, plan, batch=batch)
        sp.fence((states, traj))
    # (B, n, ...) -> (n, B, ...): moveaxis, not .T, so replicated engines'
    # per-replica observable vectors keep their trailing axis intact
    with tel.span("measure.fetch", engine=engine.name,
                  n_measure=plan.n_measure, batch=batch):
        traj = {k: np.moveaxis(np.asarray(v), 0, 1)
                for k, v in traj.items()}
    return states, traj, step_count + plan.total_sweeps
