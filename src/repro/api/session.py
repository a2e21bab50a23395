"""Session: one façade that executes any :class:`~repro.api.spec.RunSpec`.

``Session.open(spec)`` inspects the spec shape and builds the matching
runner (DESIGN.md S10):

* single      -- the registry engine advanced in place (the legacy
                 ``Simulation`` logic lives here now);
* ensemble    -- every (temperature, seed) member advanced in ONE
                 vmapped, jit-compiled sweep (the legacy ``Ensemble``
                 logic lives here now);
* sharded     -- the ``repro.core.distributed`` step named by the
                 engine's ``dist_factory`` flag on a ``MeshSpec`` mesh.

All three share one checkpoint layout: an atomically-renamed ``.npz``
holding ``spec_json`` (the lossless serialized spec), ``step_count``,
and the engine's named state arrays (batched along axis 0 for
ensembles).  ``Session.restore(path)`` needs nothing but the file: the
spec inside it rebuilds the engine, the runner, and -- for counter-based
engines -- the exact Philox stream, so a restored run continues
bit-for-bit (fault-tolerance contract, tests/test_api.py).

``describe(spec)`` is the dry-run: the dispatch decision, capability
flags, resident-tier plan, and sweep totals as one dict, computed
without touching device state (``python -m repro run --dry-run``).
"""
from __future__ import annotations

import json
import os
import tempfile
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

import repro.telemetry as tel
from repro.core.engine import ENGINES, make_engine
from repro.resilience import degrade

from .spec import RunSpec

#: default for ``Session.restore(mesh=...)``: keep the checkpoint's mesh
_KEEP = object()

#: ``Engine.dist_factory`` flag -> ``repro.core.distributed`` factory name
_DIST_FACTORIES = {
    "basic": "make_ising_step",
    "packed": "make_packed_ising_step",
    "bitplane": "make_bitplane_ising_step",
}


def _atomic_savez(path: str, **arrays) -> None:
    """Write-temp-then-rename .npz (the ``sim.save`` semantics): a killed
    writer never leaves a readable-but-partial checkpoint."""
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path) or ".",
                               suffix=".tmp")
    with os.fdopen(fd, "wb") as f:
        np.savez(f, **arrays)
    os.replace(tmp, path)


# ---------------------------------------------------------------------------
# runners: one per dispatch mode
# ---------------------------------------------------------------------------

class _SingleRunner:
    """One lattice, engine advanced in place (ex-``Simulation`` core)."""

    mode = "single"

    def __init__(self, spec: RunSpec, state=None, step_count: int = 0):
        self.spec = spec
        self.cfg = spec.sim_config()
        self.engine = make_engine(self.cfg)
        self.step_count = step_count
        self.state = self.engine.init_state(
            jax.random.PRNGKey(self.cfg.seed)) if state is None else state

    def run(self, n_sweeps: int):
        self.state = self.engine.sweeps(self.state, n_sweeps,
                                        self.step_count)
        self.step_count += n_sweeps
        return None

    def measure(self, plan) -> dict:
        from repro.analysis.measure import measure_scan
        self.state, traj, self.step_count = measure_scan(
            self.engine, self.state, plan, step_count=self.step_count)
        return traj

    def magnetization(self) -> float:
        return float(self.engine.magnetization(self.state))

    def energy(self) -> float:
        return float(self.engine.energy(self.state))

    def full_lattice(self):
        return self.engine.full_lattice(self.state)

    def state_arrays(self) -> dict:
        return self.engine.state_arrays(self.state)

    def load_arrays(self, arrays: dict) -> None:
        self.state = self.engine.from_arrays(arrays)


class _EnsembleRunner:
    """A (temperature, seed) batch advanced in ONE vmapped sweep
    (ex-``Ensemble`` core).

    Bit-exactness contract: member ``i`` follows exactly the trajectory
    of the single-mode spec with ``temperature=members[i][0],
    seed=members[i][1]`` (seeds are validated < 2**32 by ``BatchSpec``,
    so the uint32 cast below is lossless).
    """

    mode = "ensemble"

    def __init__(self, spec: RunSpec, state=None, step_count: int = 0):
        self.spec = spec
        self.cfg = spec.sim_config()
        self.engine = make_engine(self.cfg)
        self.step_count = step_count
        self._jit_cache = {}
        # jitted once per RUNNER, not per batch: jit caches on these fn
        # objects, so rebind() re-initializes a new member set without
        # retracing (the serve compiled-executable cache rides on this)
        self._init_states = jax.jit(jax.vmap(self.engine.init_state))
        self._magnetizations = jax.jit(jax.vmap(self.engine.magnetization))
        self._full_lattices = jax.jit(jax.vmap(self.engine.full_lattice))
        self._set_members(spec)
        if state is None:
            state = self._fresh_states()
        self.states = state

    def _set_members(self, spec: RunSpec) -> None:
        temps = spec.batch.member_temperatures
        seeds = spec.batch.member_seeds
        self.temperatures = np.asarray(temps, np.float32)
        # invert in python-float precision exactly like SimConfig.inv_temp
        # (1.0/float32(T) can land 1 ulp off float32(1.0/T), which would
        # eventually fork a member from its single-mode trajectory)
        self.inv_temps = jnp.asarray([1.0 / float(t) for t in temps],
                                     jnp.float32)
        self.seeds = jnp.asarray(np.asarray(seeds, np.int64) & 0xFFFFFFFF,
                                 jnp.uint32)
        self._member_seeds = tuple(int(s) for s in seeds)

    def _fresh_states(self):
        keys = jax.vmap(jax.random.PRNGKey)(
            jnp.asarray(np.asarray(self._member_seeds), jnp.int32))
        return self._init_states(keys)

    def rebind(self, spec: RunSpec) -> None:
        """Re-point this runner at a NEW (temperature, seed) batch of
        the SAME shape: same engine + params, same lattice, same batch
        size.  Keeps the engine and every jit cache -- because
        ``sweep_fn`` takes ``inv_temp``/``seed``/``start_offset`` as
        traced arguments, the compiled executables are member-agnostic
        and the rebound batch runs with zero recompilation.  This is
        the serve scheduler's compiled-executable cache primitive."""
        if spec.mode != "ensemble":
            raise ValueError(
                f"rebind needs an ensemble spec, got mode={spec.mode!r}")
        old, new = self.spec, spec
        same = (old.engine.to_dict() == new.engine.to_dict()
                and old.lattice.to_dict() == new.lattice.to_dict()
                and old.batch.size == new.batch.size)
        if not same:
            raise ValueError(
                f"rebind shape mismatch: cached runner is "
                f"{old.engine.name}/{old.lattice.n}x{old.lattice.m}/"
                f"B{old.batch.size}, spec wants "
                f"{new.engine.name}/{new.lattice.n}x{new.lattice.m}/"
                f"B{new.batch.size}")
        self.spec = spec
        self._set_members(spec)
        self.states = self._fresh_states()
        self.step_count = 0

    @property
    def size(self) -> int:
        return int(self.temperatures.size)

    def _compiled(self, n_sweeps: int):
        fn = self._jit_cache.get(n_sweeps)
        if fn is None:
            def one(state, inv_temp, seed, start_offset):
                state = self.engine.sweep_fn(state, inv_temp, seed,
                                             start_offset, n_sweeps)
                return state, self.engine.magnetization(state)

            fn = jax.jit(jax.vmap(one, in_axes=(0, 0, 0, None)))
            self._jit_cache[n_sweeps] = fn
        return fn

    def run(self, n_sweeps: int) -> np.ndarray:
        """Advance every member in one vmapped call; returns the (B,)
        per-member magnetizations (at fixed seeds this IS the
        magnetization-vs-temperature curve).

        Launched through ``resilience.degrade.run_dispatch``: a
        resident-tier demotion clears this runner's jit cache too
        (``on_demote``), so the retry re-traces ``sweep_fn`` on the
        fallback tier."""
        def attempt():
            fresh = n_sweeps not in self._jit_cache
            fn = self._compiled(n_sweeps)
            with self.engine._dispatch(
                    n_sweeps, batch=self.size,
                    compile="first" if fresh else "steady",
                    **self.engine.resident_attrs) as sp:
                states, mags = fn(
                    self.states, self.inv_temps, self.seeds,
                    jnp.uint32(2 * self.step_count))
                sp.fence(mags)
            return states, mags

        self.states, mags = degrade.run_dispatch(
            attempt, engine=self.engine,
            on_demote=self._jit_cache.clear)
        self.step_count += n_sweeps
        return np.asarray(mags)

    def measure(self, plan) -> dict:
        from repro.analysis.measure import measure_scan_batched
        self.states, traj, self.step_count = measure_scan_batched(
            self.engine, self.states, self.inv_temps, self.seeds, plan,
            step_count=self.step_count)
        return traj

    def magnetization(self) -> np.ndarray:
        """(B,) per-member magnetization of the current states."""
        return np.asarray(self._magnetizations(self.states))

    def full_lattice(self) -> np.ndarray:
        """(B, N, M) stacked +-1 lattices (measurement/debug view)."""
        return np.asarray(self._full_lattices(self.states))

    def state_arrays(self) -> dict:
        """Engine-named arrays with the batch axis leading -- the same
        names as a single checkpoint, one rank higher."""
        return {k: np.asarray(v) for k, v in
                self.engine.state_arrays(self.states).items()}

    def load_arrays(self, arrays: dict) -> None:
        # from_arrays is shape-agnostic per leaf, so batched arrays
        # rebuild the batched pytree directly
        self.states = self.engine.from_arrays(arrays)


#: ``Engine.dist_factory`` flag -> (plane cells per row given lattice
#: m, bytes per cell) -- the per-half-sweep tier's halo-traffic
#: geometry (``halo_bytes`` accounting; the sharded resident tier
#: carries its own in ``ShardPlan``)
_DIST_CELLS = {
    "basic": (lambda m: m // 2, 1),
    "packed": (lambda m: m // 16, 4),
    "bitplane": (lambda m: m // 2, 4),
}


class _ShardedRunner:
    """A ``MeshSpec`` mesh run: the sharded resident tier
    (``repro.dist``, DESIGN.md S15) when the shard planner fits the
    engine's resident family, else the per-half-sweep
    ``repro.core.distributed`` step named by ``dist_factory``.

    Randomness is global-position-keyed Philox on BOTH tiers, so the
    trajectory is bit-identical to the single-device engine on ANY
    device grid (tests/test_distributed.py, tests/test_dist.py); this
    runner only owns mesh construction, tier routing, sharding
    placement, and offset/halo bookkeeping.
    """

    mode = "sharded"

    def __init__(self, spec: RunSpec, state=None, step_count: int = 0):
        from repro.core import distributed as dist
        from repro.launch.mesh import make_mesh
        self.spec = spec
        self.cfg = spec.sim_config()
        self.engine = make_engine(self.cfg)
        ms = spec.mesh
        if ms.n_devices > jax.device_count():
            raise ValueError(
                f"MeshSpec{ms.shape} needs {ms.n_devices} devices; "
                f"{jax.device_count()} available")
        self.mesh = make_mesh(ms.shape, ms.axis_names)
        self._factory = getattr(dist,
                                _DIST_FACTORIES[self.engine.dist_factory])
        # the basic step takes its start offset in SWEEP units
        # (half_sweep_offset(0, sweep0 + i, c)); packed/bitplane and
        # the sharded resident tier take half-sweep units
        # (half_sweep_offset(sweep0, i, c))
        self._offset_scale = 1 if self.engine.dist_factory == "basic" \
            else 2
        # device grid under the default axis split (rows over all mesh
        # axes but the last, columns over the last)
        self._rows_devs = 1
        for d in ms.shape[:-1]:
            self._rows_devs *= d
        self._cols_devs = ms.shape[-1]
        self._dist_plan = None
        self._dist_attrs = {}
        if getattr(self.engine, "resident_family", None) is not None:
            from repro import dist as rdist
            fam = self.engine.resident_family
            self._dist_plan = rdist.plan_shard_resident(
                fam, self.cfg.n, self.cfg.m, self._rows_devs,
                self._cols_devs)
            self._dist_attrs = rdist.shard_decision_attrs(
                fam, self.cfg.n, self.cfg.m, self._rows_devs,
                self._cols_devs)
        self.step_count = step_count
        self._jit_cache = {}
        self._sharding = None  # set by the first step build
        if state is None:
            state = self.engine.init_state(
                jax.random.PRNGKey(self.cfg.seed))
        step, sh = self._step(1)  # build once: places state on the mesh
        self.state = tuple(jax.device_put(p, sh) for p in state)

    def _step(self, n_sweeps: int):
        got = self._jit_cache.get(n_sweeps)
        if got is None:
            if self._dist_plan is not None:
                from repro import dist as rdist
                got = rdist.make_resident_step(
                    self.mesh, self._dist_plan, seed=self.cfg.seed,
                    n_sweeps=n_sweeps)
            else:
                got = self._factory(self.mesh, n=self.cfg.n,
                                    m=self.cfg.m, seed=self.cfg.seed,
                                    n_sweeps=n_sweeps)
            self._jit_cache[n_sweeps] = got
            self._sharding = got[1]
        return got

    def _on_demote(self) -> None:
        """Resident-tier demotion (``degrade.run_dispatch``): drop to
        the per-half-sweep distributed step -- bit-exact by the shared
        global-position Philox keying -- and refresh the span attrs so
        traces show the fallback and its reason."""
        self._jit_cache.clear()
        if self._dist_plan is not None:
            from repro import dist as rdist
            self._dist_plan = None
            self._dist_attrs = rdist.shard_decision_attrs(
                self.engine.resident_family, self.cfg.n, self.cfg.m,
                self._rows_devs, self._cols_devs)

    def _record_halo(self, n_sweeps: int) -> int:
        """Account this dispatch's halo traffic into the telemetry
        counters; returns the exchange-event count (span attr + the
        S15 one-exchange-per-k-sweeps assertion in tests)."""
        if self._dist_plan is not None:
            ex = self._dist_plan.exchanges(n_sweeps)
            tel.record_halo_exchange(
                ex, ex * self._dist_plan.halo_bytes_per_exchange)
            return ex
        # per-half-sweep tier: one exchange event per half-sweep, four
        # 1-wide strips of the opposite-color plane per event
        width_of, cell = _DIST_CELLS[self.engine.dist_factory]
        n_loc = self.cfg.n // self._rows_devs
        w_loc = width_of(self.cfg.m) // self._cols_devs
        ex = 2 * n_sweeps
        per_event = (2 * n_loc + 2 * w_loc) * cell \
            * self._rows_devs * self._cols_devs
        tel.record_halo_exchange(ex, ex * per_event)
        return ex

    def run(self, n_sweeps: int):
        def attempt():
            # the host side of one sharded call: the step lookup, the
            # scalar arguments placed for the mesh, the dispatch
            with tel.span("sharded.call", k=n_sweeps,
                          mesh=list(self.spec.mesh.shape)):
                fresh = n_sweeps not in self._jit_cache
                scale = 2 if self._dist_plan is not None \
                    else self._offset_scale
                step, sh = self._step(n_sweeps)
                with tel.span("sharded.args"):
                    beta = jnp.float32(self.cfg.inv_temp)
                    offset = jnp.uint32(scale * self.step_count)
                with self.engine._dispatch(
                        n_sweeps, compile="first" if fresh else "steady",
                        mesh=list(self.spec.mesh.shape),
                        **self._dist_attrs) as sp:
                    state = step(*self.state, beta, offset)
                    sp.set(halo_exchanges=self._record_halo(n_sweeps))
                    sp.fence(state)
            return state

        self.state = degrade.run_dispatch(attempt, engine=self.engine,
                                          on_demote=self._on_demote)
        self.step_count += n_sweeps
        return None

    def measure(self, plan) -> dict:
        """Per-sample dispatch (no fused scan on the sharded path yet):
        thermalize, then ``n_measure`` (run; observe) rounds."""
        beta = jnp.float32(self.cfg.inv_temp)
        # validate the requested fields BEFORE any device sweeps (the
        # fused single/ensemble paths fail at trace time; match them)
        missing = set(plan.fields) - set(
            self.engine.observables(self.state, beta))
        if missing:
            raise ValueError(
                f"plan fields {sorted(missing)} not in engine "
                f"{self.engine.name!r} observables")
        if plan.thermalize:
            self.run(plan.thermalize)
        samples = []
        for _ in range(plan.n_measure):
            self.run(plan.sweeps_between)
            o = self.engine.observables(self.state, beta)
            samples.append({k: np.asarray(o[k], np.float32)
                            for k in plan.fields})
        return {k: np.stack([s[k] for s in samples])
                for k in plan.fields}

    def magnetization(self) -> float:
        return float(self.engine.magnetization(self.state))

    def energy(self) -> float:
        return float(self.engine.energy(self.state))

    def full_lattice(self):
        return self.engine.full_lattice(self.state)

    def state_arrays(self) -> dict:
        return {k: np.asarray(v) for k, v in
                self.engine.state_arrays(self.state).items()}

    def load_arrays(self, arrays: dict) -> None:
        state = self.engine.from_arrays(arrays)
        self.state = tuple(jax.device_put(p, self._sharding)
                           for p in state)


_RUNNERS = {"single": _SingleRunner, "ensemble": _EnsembleRunner,
            "sharded": _ShardedRunner}


# ---------------------------------------------------------------------------
# dry-run plan
# ---------------------------------------------------------------------------

def describe(spec: RunSpec) -> dict:
    """The validated dispatch plan as one dict -- no device work.

    This is what ``python -m repro run --dry-run`` prints: which runner
    the spec selects, the registry capability flags it was validated
    against, the resident-tier decision for the lattice, and the total
    sweep budget.
    """
    cls = ENGINES[spec.engine.name]
    resident = None
    dist_plan = None
    with tel.span("spec.validate", mode=spec.mode,
                  engine=spec.engine.name,
                  lattice=(spec.lattice.n, spec.lattice.m)):
        if getattr(cls, "resident_family", None) is not None:
            from repro.kernels.resident import decision_attrs
            # the ONE rendering of the planner decision: this dict is
            # the --dry-run output AND the planner.decide/dispatch span
            # attributes (satellite: dry-run and traces cannot disagree)
            resident = decision_attrs(cls.resident_family,
                                      spec.lattice.n, spec.lattice.m)
            tel.instant("planner.decide", **resident)
            if spec.mesh is not None:
                # sharded runs use the SHARD planner (S15): same
                # single-rendering contract as "resident" above
                from repro.dist import shard_decision_attrs
                rows_devs = 1
                for d in spec.mesh.shape[:-1]:
                    rows_devs *= d
                dist_plan = shard_decision_attrs(
                    cls.resident_family, spec.lattice.n,
                    spec.lattice.m, rows_devs, spec.mesh.shape[-1])
                tel.instant("planner.decide_shard", **dist_plan)
    out = {
        "mode": spec.mode,
        "engine": spec.engine.name,
        "engine_params": spec.engine.param_dict,
        "counter_based": cls.counter_based,
        "replicas": cls.replicas,
        "dist_factory": cls.dist_factory,
        "resident": resident,
        "dist": dist_plan,
        "lattice": [spec.lattice.n, spec.lattice.m],
        "init_p_up": spec.lattice.init_p_up,
        "batch_size": 1 if spec.batch is None else spec.batch.size,
        "mesh": None if spec.mesh is None else spec.mesh.to_dict(),
        "total_sweeps": None if spec.sweep is None
        else spec.sweep.total_sweeps,
        "spec": spec.to_dict(),
    }
    if spec.batch is not None:
        out["members"] = [list(p) for p in spec.batch.members]
    return out


# ---------------------------------------------------------------------------
# the façade
# ---------------------------------------------------------------------------

class Session:
    """Open a spec, run it, measure it, checkpoint it -- any mode.

    ``run``/``measure``/``magnetization``/``full_lattice`` return
    single-valued results in single/sharded mode and batch-axis results
    in ensemble mode (``run`` additionally returns the (B,) per-member
    magnetizations there: one fused dispatch yields the m(T) curve).
    """

    def __init__(self, spec: RunSpec, runner=None):
        self.spec = spec
        if runner is not None:
            self._runner = runner
        else:
            with tel.span("session.open", mode=spec.mode,
                          engine=spec.engine.name,
                          lattice=(spec.lattice.n, spec.lattice.m),
                          batch=1 if spec.batch is None
                          else spec.batch.size) as sp:
                self._runner = _RUNNERS[spec.mode](spec)
                sp.fence(self.state)

    @classmethod
    def open(cls, spec: RunSpec) -> "Session":
        return cls(spec)

    # -- delegated state ----------------------------------------------------
    @property
    def mode(self) -> str:
        return self._runner.mode

    @property
    def engine(self):
        return self._runner.engine

    @property
    def state(self):
        """The engine-native state pytree (batch axis leading in
        ensemble mode) -- the public window the examples/tests use
        instead of reaching into runner internals."""
        return self._runner.states if self.mode == "ensemble" \
            else self._runner.state

    @state.setter
    def state(self, v) -> None:
        if self.mode == "ensemble":
            self._runner.states = v
        else:
            self._runner.state = v

    @property
    def step_count(self) -> int:
        return self._runner.step_count

    @step_count.setter
    def step_count(self, v: int) -> None:
        self._runner.step_count = v

    # -- execution ----------------------------------------------------------
    def run(self, n_sweeps: int):
        """Advance ``n_sweeps`` full lattice sweeps (every member, in
        ensemble mode).  Ensemble mode returns the (B,) per-member
        magnetizations of the fused sweep dispatch."""
        with tel.span("session.run", mode=self.mode,
                      engine=self.spec.engine.name, k=n_sweeps) as sp:
            out = self._runner.run(n_sweeps)
            sp.fence(self.state)
        return out

    def measure(self, plan=None) -> dict:
        """Run a measurement plan; defaults to ``spec.sweep``.

        Returns ``{field: (n_measure, ...) float32 ndarray}`` --
        trailing batch axis in ensemble mode, trailing replica axis for
        replicated engines.
        """
        if plan is None:
            if self.spec.sweep is None:
                raise ValueError(
                    "no plan: pass one or set RunSpec.sweep")
            plan = self.spec.sweep.plan()
        with tel.span("session.measure", mode=self.mode,
                      engine=self.spec.engine.name,
                      n_measure=plan.n_measure,
                      sweeps_between=plan.sweeps_between,
                      thermalize=plan.thermalize) as sp:
            traj = self._runner.measure(plan)
            sp.fence(self.state)
        return traj

    def trajectory(self, n_measure: int, sweeps_between: int,
                   thermalize: int = 0) -> np.ndarray:
        """Magnetization samples via the fused scan (shape
        ``(n_measure,)``; + batch/replica axes per mode/engine)."""
        from repro.analysis.measure import MeasurementPlan
        plan = MeasurementPlan(n_measure, sweeps_between, thermalize,
                               fields=("m",))
        return self.measure(plan)["m"]

    def magnetization(self):
        return self._runner.magnetization()

    def energy(self):
        return self._runner.energy()

    def full_lattice(self):
        return self._runner.full_lattice()

    def plan(self) -> dict:
        """The dispatch plan of this session's spec (:func:`describe`)."""
        return describe(self.spec)

    # -- fault tolerance ----------------------------------------------------
    def state_digest(self, member: Optional[int] = None) -> str:
        """CRC32C hex digest of (step_count, every named state array):
        two sessions with equal digests hold bit-identical lattices at
        the same point of the trajectory.  The bit-exact-resume tests
        and the CI chaos job compare exactly this string.

        ``member`` (ensemble mode only) digests ONE member's slice of
        the batched state with the same framing a single-mode session
        uses -- by the ensemble bit-exactness contract the result
        equals the digest of the equivalent single run, which is how
        the serve layer proves a coalesced job matches a direct one."""
        from repro.resilience import integrity
        arrays = self._runner.state_arrays()
        if member is not None:
            if self.mode != "ensemble":
                raise ValueError(
                    f"member= digest needs ensemble mode, this session "
                    f"is {self.mode!r}")
            if not 0 <= member < self._runner.size:
                raise ValueError(
                    f"member {member} out of range for batch size "
                    f"{self._runner.size}")
            arrays = {k: np.asarray(v)[member]
                      for k, v in arrays.items()}
        crc = integrity.crc32c(
            f"step_count={self._runner.step_count}".encode())
        for k, v in sorted(arrays.items()):
            a = np.ascontiguousarray(np.asarray(v))
            crc = integrity.crc32c(
                f"{k}:{a.dtype}:{a.shape}:".encode(), crc)
            crc = integrity.crc32c(a.tobytes(), crc)
        return f"{crc:08x}"

    def save(self, path: str, extra: Optional[dict] = None) -> None:
        """Atomic checkpoint: serialized spec + step count + the
        engine's named state arrays (batched in ensemble mode).
        ``extra`` adds scalar/str fields (the legacy shims pass their
        pre-spec metadata through it)."""
        with tel.span("ckpt.save", path=path, mode=self.mode,
                      step_count=self._runner.step_count):
            arrays = {f"state_{k}": v
                      for k, v in self._runner.state_arrays().items()}
            _atomic_savez(path, spec_json=self.spec.to_json(),
                          step_count=self._runner.step_count,
                          **(extra or {}), **arrays)

    @classmethod
    def restore(cls, path: str, mesh=_KEEP) -> "Session":
        """Rebuild a session from a checkpoint alone: the embedded spec
        reconstructs engine + runner, the arrays restore the state, and
        counter-based engines continue the exact Philox stream.

        ``mesh`` overrides the checkpoint's ``MeshSpec`` (pass a
        ``MeshSpec`` to reshard, ``None`` to continue single-device).
        Legal because sharded trajectories are keyed on GLOBAL lattice
        positions (DESIGN.md S15 stream invariance): the device grid
        is an execution detail, not part of the trajectory's identity,
        so a checkpoint saved on one mesh continues bit-exactly on any
        other (tests/test_dist.py cross-mesh portability)."""
        import dataclasses as _dc
        with tel.span("ckpt.restore", path=path) as sp:
            spec, step_count, arrays, _ = _load_checkpoint(path)
            if mesh is not _KEEP and mesh != spec.mesh:
                spec = _dc.replace(spec, mesh=mesh)
            sp.set(mode=spec.mode, engine=spec.engine.name,
                   step_count=step_count)
            return cls._from_arrays(spec, arrays, step_count)

    @classmethod
    def _from_arrays(cls, spec: RunSpec, arrays: dict,
                     step_count: int) -> "Session":
        with tel.span("session.build", mode=spec.mode,
                      engine=spec.engine.name,
                      lattice=(spec.lattice.n, spec.lattice.m),
                      step_count=step_count) as sp:
            runner = _RUNNERS[spec.mode](spec, state=_SENTINEL,
                                         step_count=step_count)
            runner.load_arrays(arrays)
            session = cls(spec, runner=runner)
            sp.fence(session.state)
        return session


#: placeholder state handed to runner __init__ so restore skips the
#: (potentially expensive) fresh init before load_arrays overwrites it
_SENTINEL = ()


def load_spec(path: str) -> RunSpec:
    """Read ONLY the embedded spec of a checkpoint -- the state arrays
    stay on disk (NpzFile decompresses lazily per entry), so a dry-run
    or spec inspection of a huge ensemble checkpoint costs nothing."""
    with np.load(path, allow_pickle=False) as z:
        if "spec_json" in z.files:
            return RunSpec.from_json(str(z["spec_json"]))
        if "config_json" in z.files:
            from repro.core.sim import SimConfig
            return RunSpec.from_sim_config(
                SimConfig(**json.loads(str(z["config_json"]))))
    raise ValueError(
        f"{path}: not a checkpoint in the registry layout (missing "
        "'spec_json'/'config_json'; pre-registry .npz files are not "
        "restorable by this release)")


def _load_checkpoint(path: str):
    """Read a unified checkpoint: (spec, step_count, state arrays,
    legacy config dict or None).  Accepts the PR-4-era single-simulation
    layout (``config_json`` only) by lifting the config into a spec."""
    with np.load(path, allow_pickle=False) as z:
        legacy = None
        if "config_json" in z.files:
            legacy = json.loads(str(z["config_json"]))
        if "spec_json" in z.files:
            spec = RunSpec.from_json(str(z["spec_json"]))
        elif legacy is not None:
            from repro.core.sim import SimConfig
            spec = RunSpec.from_sim_config(SimConfig(**legacy))
        else:
            raise ValueError(
                f"{path}: not a checkpoint in the registry layout "
                "(missing 'spec_json'/'config_json'; pre-registry .npz "
                "files are not restorable by this release)")
        step_count = int(z["step_count"])
        arrays = {k[len("state_"):]: z[k] for k in z.files
                  if k.startswith("state_")}
    return spec, step_count, arrays, legacy
