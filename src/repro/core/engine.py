"""Pluggable engine registry: one protocol, ten update algorithms.

The paper's contribution is *comparing implementations* of the same 2D
Ising Metropolis update; this module is the seam that makes the
implementations interchangeable (DESIGN.md S3).  Every engine subclasses
:class:`Engine` and registers itself in :data:`ENGINES` under its paper
name; the :class:`~repro.core.sim.Simulation` driver and the
:class:`~repro.core.ensemble.Ensemble` batched driver dispatch purely
through the registry, so adding an engine never touches the drivers.

Protocol (all methods pure in the JAX sense unless noted):

* ``init_state(key)``        -- PRNG key -> engine-native state pytree;
* ``sweeps(state, n, step)`` -- advance ``n`` full lattice sweeps (stateful
                                wrapper: owns jit caching / RNG offsets);
* ``full_lattice(state)``    -- state -> the (N, M) +-1 int8 lattice;
* ``magnetization(state)``   -- mean spin (scalar array);
* ``state_arrays(state)``    -- state -> {name: np.ndarray} for .npz;
* ``from_arrays(arrays)``    -- inverse of ``state_arrays``.

Counter-based engines (Philox randomness addressed by (seed, position,
offset), cuRAND semantics -- DESIGN.md S4) additionally expose
``sweep_fn(state, inv_temp, seed, start_offset, n_sweeps)``: a pure
function with *traceable* seed and temperature, which is what the
ensemble driver ``vmap``s over a (temperature, seed) batch axis.

Two hooks added for the measurement subsystem (DESIGN.md S7):

* ``observables(state, inv_temp)`` -- pure, trace/vmap-safe map of the
  engine-native state to ``{"m": mean spin, "e": energy/spin}``; the
  default routes through ``full_lattice``, so it is correct for every
  layout (packed words, tensor-core planes, ...) -- engines with a
  cheaper or physically different path override it: the multispin
  engines count exactly on the packed words, the spin glass weights
  its couplings;
* ``scan_step(state, inv_temp, seed, step_count, n_sweeps)`` -- pure
  version of ``sweeps`` with a *traceable* cumulative-sweep counter, the
  unit that ``repro.analysis.measure.measure_scan`` chains inside one
  ``jax.lax.scan``.  ``sweeps`` (the stateful wrapper) and ``scan_step``
  must draw the same random stream or trajectories would fork between
  the legacy per-sample loop and the fused scan (tested bit-exact).
"""
from __future__ import annotations

from contextlib import contextmanager
from typing import Callable, ClassVar, Dict, Optional, Type

import jax
import jax.numpy as jnp
import numpy as np

import repro.telemetry as tel
from repro.resilience import degrade

from . import bitplane as bp
from . import lattice as lat
from . import metropolis as metro
from . import multispin as ms
from . import observables as obs
from . import rng as crng
from . import spinglass as sg
from . import tensorcore as tc
from . import wolff as wolff_mod

ENGINES: Dict[str, Type["Engine"]] = {}


def register(cls: Type["Engine"]) -> Type["Engine"]:
    """Class decorator: add an engine to the registry under ``cls.name``."""
    assert cls.name not in ENGINES, f"duplicate engine {cls.name!r}"
    ENGINES[cls.name] = cls
    return cls


def make_engine(config) -> "Engine":
    """Instantiate the registered engine named by ``config.engine``."""
    try:
        cls = ENGINES[config.engine]
    except KeyError:
        raise ValueError(
            f"unknown engine {config.engine!r}; registered engines: "
            f"{sorted(ENGINES)}") from None
    return cls(config)


class Engine:
    """Base class: holds the config, defines the protocol and defaults."""

    name: ClassVar[str]
    counter_based: ClassVar[bool] = False  # True: vmap-safe Philox sweeps
    #: independent replica chains carried per state (1 for every engine
    #: except bitplane, whose observables are per-replica vectors)
    replicas: ClassVar[int] = 1
    #: engine-specific config knobs this engine actually consumes --
    #: ``repro.api.EngineSpec`` validates its params against this set at
    #: construction time (DESIGN.md S10)
    param_fields: ClassVar[tuple] = ()
    #: name of the ``repro.core.distributed`` step factory that advances
    #: this engine's random stream on a device mesh (``None`` = no
    #: sharded execution); the capability flag behind ``MeshSpec``
    dist_factory: ClassVar[Optional[str]] = None
    #: how :meth:`observables` reads the state, for the ``measure_scan``
    #: span: ``"full"`` through ``full_lattice``, ``"packed"`` from the
    #: packed words
    observables_path: ClassVar[str] = "full"

    @classmethod
    def validate_lattice(cls, n: int, m: int) -> None:
        """Raise ValueError when (n, m) violates this engine's layout
        constraints -- called by ``RunSpec`` at construction, so bad
        geometry fails before any trace (DESIGN.md S10)."""
        if n % 2 or m % 2:
            raise ValueError(
                f"engine {cls.name!r} needs even lattice dims for the "
                f"checkerboard decomposition, got ({n}, {m})")

    def __init__(self, config):
        self.cfg = config

    # -- construction -------------------------------------------------------
    def init_state(self, key):
        """Fresh state from a PRNG key (vmap-safe for batched init)."""
        cfg = self.cfg
        full = lat.init_lattice(key, cfg.n, cfg.m, p_up=cfg.init_p_up)
        return self.from_full(full)

    def from_full(self, full):
        """(N, M) +-1 lattice -> engine-native state pytree."""
        raise NotImplementedError

    # -- views --------------------------------------------------------------
    def full_lattice(self, state):
        raise NotImplementedError

    def magnetization(self, state):
        b, w = lat.split_checkerboard(self.full_lattice(state))
        return obs.magnetization(b, w)

    def energy(self, state):
        return self.observables(state, jnp.float32(self.cfg.inv_temp))["e"]

    def observables(self, state, inv_temp):
        """Pure, trace/vmap-safe observables of the engine-native state.

        Returns ``{"m": mean spin, "e": energy per spin}``.  The default
        goes through ``full_lattice``, which is layout-correct for every
        engine; ``inv_temp`` is part of the contract so engines can add
        temperature-dependent observables without changing call sites.
        """
        full = self.full_lattice(state)
        return {"m": obs.magnetization_full(full),
                "e": obs.energy_per_spin_full(full)}

    # -- dynamics -----------------------------------------------------------
    @contextmanager
    def _dispatch(self, n_sweeps: int, batch: int = 1, **attrs):
        """Account + trace ONE compiled-call invocation.

        Every stateful ``sweeps`` wrapper (and the batched runners)
        launches its compiled call inside this scope: the canonical
        counters advance unconditionally (host-side, once per call --
        NEVER inside traced code), and when tracing is on a fenced
        ``dispatch`` span records the phase.  ``sp.fence(out)`` inside
        the ``with`` makes the span wait for device completion.
        """
        tel.record_dispatch(n_sweeps=n_sweeps,
                            sites=self.cfg.n * self.cfg.m,
                            replicas=self.replicas, batch=batch,
                            counter_based=self.counter_based)
        with tel.span("dispatch", engine=self.name,
                      lattice=(self.cfg.n, self.cfg.m), k=n_sweeps,
                      replicas=self.replicas, batch=batch,
                      **attrs) as sp:
            yield sp

    def sweeps(self, state, n_sweeps: int, step_count: int):
        """Default stateful wrapper: ``scan_step`` at the config's own
        temperature and seed, accounted as ONE dispatch.  Engines owning
        their jit caching (CounterEngine) override it.

        Launched through ``resilience.degrade.run_dispatch``: transient
        failures retry with bounded backoff; each (re)attempt is its
        own accounted dispatch.
        """
        def attempt():
            with self._dispatch(n_sweeps) as sp:
                out = self.scan_step(state,
                                     jnp.float32(self.cfg.inv_temp),
                                     self.cfg.seed, step_count, n_sweeps)
                sp.fence(out)
            return out

        return degrade.run_dispatch(attempt, engine=self)

    def scan_step(self, state, inv_temp, seed, step_count, n_sweeps: int):
        """Pure ``sweeps``: advance ``n_sweeps`` (static) from a traceable
        cumulative ``step_count``; must reproduce ``sweeps`` bit-for-bit."""
        raise NotImplementedError

    # -- checkpointing ------------------------------------------------------
    def state_arrays(self, state) -> dict:
        raise NotImplementedError

    def from_arrays(self, arrays: dict):
        raise NotImplementedError


class CounterEngine(Engine):
    """Shared machinery for counter-based (Philox skip-ahead) engines.

    Subclasses implement ``color_update`` (one half-sweep of the target
    plane); this base owns the 2-half-sweeps-per-sweep offset bookkeeping
    behind the stateful ``sweeps`` protocol method, plus per-``n_sweeps``
    jit caching.  The offset scheme must stay identical to the standalone
    ``run_sweeps_philox``/``run_sweeps_packed`` wrappers (same stream,
    cross-tied in tests/test_engines.py) or checkpoints would fork.
    """

    counter_based = True

    #: planner family key of the resident-sweep tier (DESIGN.md S9);
    #: ``None`` = engine has no resident kernel.  Pallas-backed engines
    #: set it; at construction the VMEM planner
    #: (:func:`repro.kernels.resident.plan_resident`) decides whether
    #: this lattice's planes fit per-core VMEM, and ``sweep_fn`` routes
    #: every n-sweep dispatch through ONE resident kernel call when they
    #: do -- ``Simulation``/``Ensemble``/``measure_scan`` pick the tier
    #: up through the registry with no caller changes.
    resident_family: ClassVar[Optional[str]] = None

    def __init__(self, config):
        super().__init__(config)
        self._jit_cache: Dict[int, Callable] = {}
        self.resident_plan = None
        #: the planner's decision as span attributes -- the SAME dict
        #: ``describe()`` renders in ``--dry-run``, so dry-run output
        #: and live traces can never disagree about the tier
        self.resident_attrs: dict = {}
        if self.resident_family is not None:
            from repro.kernels import resident
            self.resident_plan = resident.plan_resident(
                self.resident_family, config.n, config.m)
            self.resident_attrs = resident.decision_attrs(
                self.resident_family, config.n, config.m)
            #: row-block height of the per-half-sweep kernels
            self.block_rows = resident.block_plan(
                self.resident_family, config.n, config.m).block_rows
            self.interpret = resident.interpret_mode()

    def color_update(self, target, op, inv_temp, is_black, seed, offset,
                     ctx=None):
        """One half-sweep; ``seed`` may be a python int or uint32 trace.

        ``ctx`` receives :meth:`sweep_context`'s per-call precomputation.
        """
        raise NotImplementedError

    def sweep_context(self, inv_temp):
        """Loop-invariant precomputation (e.g. the integer acceptance
        thresholds, H1.6) evaluated ONCE per sweep call and passed to
        every ``color_update`` -- structurally hoisted out of the
        fori_loop rather than left to XLA's LICM."""
        return None

    def resident_sweeps(self, state, inv_temp, seed, start_offset,
                        n_sweeps: int):
        """Resident-tier dispatch (DESIGN.md S9): ``n_sweeps`` FULL
        sweeps in ONE kernel call, both planes VMEM-resident, Philox
        advanced in-kernel with the same (sweep, color) counter layout
        (``rng.half_sweep_offset``) as the fallback loop below -- must
        be bit-exact vs ``n_sweeps`` iterations of ``color_update``."""
        raise NotImplementedError

    def sweep_fn(self, state, inv_temp, seed, start_offset, n_sweeps: int):
        """Pure sweep kernel: n_sweeps x (black, white) half-sweeps with
        cuRAND-style offsets 2i / 2i+1 past ``start_offset``.

        Tiered (DESIGN.md S9): when the construction-time VMEM plan
        exists, the whole n-sweep block is ONE resident kernel dispatch;
        otherwise the per-half-sweep ``color_update`` fori_loop runs.
        Both tiers share one Philox counter layout, so which tier ran is
        unobservable in the trajectory (tested in tests/test_resident.py).
        ``n_sweeps == 0`` takes the fallback path, whose fori_loop
        no-ops, so the zero-sweep edge behaves alike on every tier.
        """
        if self.resident_plan is not None and n_sweeps > 0:
            return tuple(self.resident_sweeps(state, inv_temp, seed,
                                              start_offset, n_sweeps))
        start = jnp.uint32(start_offset)
        ctx = self.sweep_context(inv_temp)

        def body(i, carry):
            b, w = carry
            b = self.color_update(b, w, inv_temp, True, seed,
                                  crng.half_sweep_offset(start, i, 0), ctx)
            w = self.color_update(w, b, inv_temp, False, seed,
                                  crng.half_sweep_offset(start, i, 1), ctx)
            return (b, w)

        return jax.lax.fori_loop(0, n_sweeps, body, tuple(state))

    def scan_step(self, state, inv_temp, seed, step_count, n_sweeps: int):
        # one half-sweep offset per color: cumulative offset = 2 * sweeps
        return self.sweep_fn(state, inv_temp, seed, 2 * step_count, n_sweeps)

    def _demote_resident(self, reason: str) -> None:
        """Demote this (family, lattice) to the per-half-sweep fallback
        tier for the rest of the process (DESIGN.md S13): record it in
        the process-global registry (so freshly built engines and
        ``--dry-run`` plans agree), drop the plan, re-render the span
        attributes, and invalidate the jit cache so the next dispatch
        traces ``sweep_fn``'s fallback branch.  Both tiers draw the
        same Philox stream, so the trajectory does not fork."""
        from repro.kernels.resident import decision_attrs
        degrade.demote(self.resident_family, self.cfg.n, self.cfg.m,
                       reason)
        self.resident_plan = None
        self.resident_attrs = decision_attrs(self.resident_family,
                                             self.cfg.n, self.cfg.m)
        self._jit_cache.clear()

    def sweeps(self, state, n_sweeps: int, step_count: int):
        def attempt():
            # fn is re-read from the cache on every (re)attempt: a
            # demotion clears the cache, so the retry traces and runs
            # the fallback tier
            fn = self._jit_cache.get(n_sweeps)
            fresh = fn is None
            if fn is None:
                # seed closed over: python int, full 64-bit keys
                seed = self.cfg.seed
                # the incoming state buffers are donated: callers
                # rebind (state = engine.sweeps(state, ...)), so large
                # lattices never hold two copies of a plane in HBM
                fn = jax.jit(lambda s, beta, off: self.sweep_fn(
                    s, beta, seed, off, n_sweeps), donate_argnums=(0,))
                self._jit_cache[n_sweeps] = fn
            with self._dispatch(n_sweeps,
                                compile="first" if fresh else "steady",
                                **self.resident_attrs) as sp:
                out = fn(state, jnp.float32(self.cfg.inv_temp),
                         jnp.uint32(2 * step_count))
                sp.fence(out)
            return out

        return degrade.run_dispatch(attempt, engine=self)


# ---------------------------------------------------------------------------
# compact color-plane engines (basic / basic_philox / stencil_pallas)
# ---------------------------------------------------------------------------

class _PlanesEngine(Engine):
    """Common state handling for (black, white) compact-plane engines."""

    def from_full(self, full):
        return tuple(lat.split_checkerboard(full))

    def full_lattice(self, state):
        return lat.merge_checkerboard(*state)

    def magnetization(self, state):
        return obs.magnetization(*state)

    def state_arrays(self, state):
        return {"black": np.asarray(state[0]), "white": np.asarray(state[1])}

    def from_arrays(self, arrays):
        return (jnp.asarray(arrays["black"]), jnp.asarray(arrays["white"]))


@register
class BasicEngine(_PlanesEngine):
    """Paper S3.1 basic checkerboard Metropolis, jax.random uniforms."""

    name = "basic"

    def scan_step(self, state, inv_temp, seed, step_count, n_sweeps):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), step_count)
        b, w, _ = metro.run_sweeps(*state, inv_temp, key, n_sweeps)
        return (b, w)



@register
class BasicPhiloxEngine(_PlanesEngine, CounterEngine):
    """Basic engine with in-place counter-based Philox (DESIGN.md S6.2)."""

    name = "basic_philox"
    dist_factory = "basic"

    def color_update(self, target, op, inv_temp, is_black, seed, offset,
                     ctx=None):
        return metro.update_color_philox(target, op, inv_temp, is_black,
                                         seed, offset)


@register
class StencilPallasEngine(_PlanesEngine, CounterEngine):
    """Fused Pallas stencil kernel (DESIGN.md S6.2); interpret mode off
    a TPU (``kernels.resident.interpret_mode``).

    Philox is keyed on the global (row, col) index, so this engine is
    bit-for-bit identical to ``basic_philox`` -- the kernel's pure-jnp
    oracle -- at any block size (tested in tests/test_engines.py).
    """

    name = "stencil_pallas"
    resident_family = "stencil"
    dist_factory = "basic"  # bit-for-bit the basic_philox stream

    def color_update(self, target, op, inv_temp, is_black, seed, offset,
                     ctx=None):
        from repro.kernels.stencil.stencil import stencil_update
        return stencil_update(target, op, inv_temp, is_black=is_black,
                              seed=seed, offset=offset,
                              block_rows=self.block_rows,
                              interpret=self.interpret)

    def resident_sweeps(self, state, inv_temp, seed, start_offset,
                        n_sweeps):
        from repro.kernels.stencil.resident import stencil_sweeps_resident
        return stencil_sweeps_resident(*state, inv_temp,
                                       n_sweeps=n_sweeps, seed=seed,
                                       start_offset=start_offset,
                                       interpret=self.interpret)


# ---------------------------------------------------------------------------
# multi-spin packed engine
# ---------------------------------------------------------------------------

@register
class MultispinEngine(CounterEngine):
    """Paper S3.3 multi-spin coding: 8 spins/uint32 word (DESIGN.md S2)."""

    name = "multispin"
    dist_factory = "packed"

    @classmethod
    def validate_lattice(cls, n, m):
        super().validate_lattice(n, m)
        if (m // 2) % lat.SPINS_PER_WORD:
            raise ValueError(
                f"engine {cls.name!r} packs {lat.SPINS_PER_WORD} "
                f"spins/uint32 word: the compact plane width m/2 must "
                f"be a multiple of {lat.SPINS_PER_WORD}, got m={m}")

    def from_full(self, full):
        return ms.pack_lattice(*lat.split_checkerboard(full))

    def full_lattice(self, state):
        return lat.merge_checkerboard(*ms.unpack_lattice(*state))

    # exact counts on the packed words: no lattice-sized int8 or float32
    # copy, bit for bit the full-lattice observables where those are exact
    observables_path = "packed"

    def magnetization(self, state):
        return ms.packed_magnetization(*state)

    def observables(self, state, inv_temp):
        return ms.packed_observables(*state)

    def sweep_context(self, inv_temp):
        return ms.acceptance_thresholds(inv_temp)

    def color_update(self, target, op, inv_temp, is_black, seed, offset,
                     ctx=None):
        return ms.update_color_packed(target, op, inv_temp, is_black,
                                      seed, offset, thresholds=ctx)

    def state_arrays(self, state):
        return {"black_words": np.asarray(state[0]),
                "white_words": np.asarray(state[1])}

    def from_arrays(self, arrays):
        return (jnp.asarray(arrays["black_words"]),
                jnp.asarray(arrays["white_words"]))


@register
class MultispinPallasEngine(MultispinEngine):
    """Fused Pallas multispin kernel (DESIGN.md S6.3) as a registry
    engine; interpret-mode on CPU.

    Philox is keyed on the global word index, so this engine is
    bit-for-bit identical to ``multispin`` -- the kernel's pure-jnp
    oracle -- at any block size, and through the resident tier (S9).
    """

    name = "multispin_pallas"
    resident_family = "multispin"

    def color_update(self, target, op, inv_temp, is_black, seed, offset,
                     ctx=None):
        from repro.kernels.multispin.multispin import multispin_update
        return multispin_update(target, op, inv_temp, is_black=is_black,
                                seed=seed, offset=offset,
                                block_rows=self.block_rows,
                                interpret=self.interpret, thresholds=ctx)

    def resident_sweeps(self, state, inv_temp, seed, start_offset,
                        n_sweeps):
        from repro.kernels.multispin.resident import \
            multispin_sweeps_resident
        return multispin_sweeps_resident(*state, inv_temp,
                                         n_sweeps=n_sweeps, seed=seed,
                                         start_offset=start_offset,
                                         interpret=self.interpret)


# ---------------------------------------------------------------------------
# bitplane engines: 32 replicas/word (DESIGN.md S8)
# ---------------------------------------------------------------------------

@register
class BitplaneEngine(CounterEngine):
    """Bitplane multi-spin coding: 32 independent replica lattices packed
    1 bit/spin into each uint32 word (DESIGN.md S8, Block et al.).

    One simulation advances 32 replicas; ``observables`` returns
    *per-replica* (32,) vectors, which flow through ``measure_scan`` and
    the estimators unchanged (the trajectory gains a trailing replica
    axis).  ``full_lattice`` is the replica-0 view, and ``init_state``
    seeds replica 0 exactly like the single-lattice engines (replica r
    folds r into the key), so the cross-engine init contract holds.
    """

    name = "bitplane"
    replicas = bp.N_REPLICAS
    dist_factory = "bitplane"

    @classmethod
    def validate_lattice(cls, n, m):
        super().validate_lattice(n, m)
        if (m // 2) % 4:
            raise ValueError(
                f"engine {cls.name!r} draws one Philox call per 4-site "
                f"group: the compact plane width m/2 must be a multiple "
                f"of 4, got m={m}")

    def init_state(self, key):
        cfg = self.cfg

        def init_one(k):
            return lat.init_lattice(k, cfg.n, cfg.m, p_up=cfg.init_p_up)

        # one jitted program: XLA fuses each replica's uniforms into its
        # int8 spins, where eager ops would hold 31 float32 lattices at
        # once (7.75 GB at 8192^2, more than a 16 GB chip has left)
        @jax.jit
        def build(key):
            r0 = init_one(key)
            keys = jax.vmap(lambda r: jax.random.fold_in(key, r))(
                jnp.arange(1, bp.N_REPLICAS))
            rest = jax.vmap(init_one)(keys)
            return bp.pack_lattices(
                jnp.concatenate([r0[None], rest], axis=0))

        return build(key)

    def from_full(self, full):
        black, white = lat.split_checkerboard(full)
        return (bp.broadcast_plane(lat.to_binary(black)),
                bp.broadcast_plane(lat.to_binary(white)))

    def full_lattice(self, state):
        return bp.replica_lattice(*state, r=0)

    def magnetization(self, state):
        # only the magnetizations: skip replica_observables' per-replica
        # energies, which an eager caller would pay for and discard
        fulls = bp.unpack_lattices(*state)
        return jnp.mean(jax.vmap(obs.magnetization_full)(fulls))

    def energy(self, state):
        # only the energies (see magnetization)
        fulls = bp.unpack_lattices(*state)
        return jnp.mean(jax.vmap(obs.energy_per_spin_full)(fulls))

    def observables(self, state, inv_temp):
        """Per-replica vectors: {"m": (32,), "e": (32,)}."""
        return bp.replica_observables(*state)

    def sweep_context(self, inv_temp):
        return ms.acceptance_thresholds(inv_temp)

    def color_update(self, target, op, inv_temp, is_black, seed, offset,
                     ctx=None):
        return bp.update_color_bitplane(target, op, inv_temp, is_black,
                                        seed, offset, thresholds=ctx)

    def state_arrays(self, state):
        return {"black_bits": np.asarray(state[0]),
                "white_bits": np.asarray(state[1])}

    def from_arrays(self, arrays):
        return (jnp.asarray(arrays["black_bits"]),
                jnp.asarray(arrays["white_bits"]))


@register
class BitplanePallasEngine(BitplaneEngine):
    """Fused Pallas bitplane kernel; interpret-mode on CPU.

    Philox is keyed on the global (site // 4, site % 4) pair, so this
    engine is bit-for-bit identical to ``bitplane`` -- the kernel's
    pure-jnp oracle -- at any block size (tests/test_bitplane.py).
    """

    name = "bitplane_pallas"
    resident_family = "bitplane"

    def color_update(self, target, op, inv_temp, is_black, seed, offset,
                     ctx=None):
        from repro.kernels.bitplane.bitplane import bitplane_update
        return bitplane_update(target, op, inv_temp, is_black=is_black,
                               seed=seed, offset=offset,
                               block_rows=self.block_rows,
                               interpret=self.interpret, thresholds=ctx)

    def resident_sweeps(self, state, inv_temp, seed, start_offset,
                        n_sweeps):
        from repro.kernels.bitplane.resident import \
            bitplane_sweeps_resident
        return bitplane_sweeps_resident(*state, inv_temp,
                                        n_sweeps=n_sweeps, seed=seed,
                                        start_offset=start_offset,
                                        interpret=self.interpret)


# ---------------------------------------------------------------------------
# tensor-core (MXU) engine
# ---------------------------------------------------------------------------

@register
class TensorCoreEngine(Engine):
    """Paper S3.2: neighbor sums as banded MXU matmuls (DESIGN.md S6.1)."""

    name = "tensorcore"
    param_fields = ("tc_block",)

    def from_full(self, full):
        return tc.decompose(full)

    def full_lattice(self, state):
        return tc.recompose(state)

    def magnetization(self, state):
        m = sum(p.astype(jnp.float32).sum() for p in state.values())
        return m / (self.cfg.n * self.cfg.m)

    def scan_step(self, state, inv_temp, seed, step_count, n_sweeps):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), step_count)
        planes, _ = tc.run_sweeps_tc(state, inv_temp, key, n_sweeps,
                                     block=self.cfg.tc_block)
        return planes


    def state_arrays(self, state):
        return {f"plane_{k}": np.asarray(v) for k, v in state.items()}

    def from_arrays(self, arrays):
        return {k: jnp.asarray(arrays[f"plane_{k}"])
                for k in ("00", "01", "10", "11")}


# ---------------------------------------------------------------------------
# Wolff cluster engine
# ---------------------------------------------------------------------------

@register
class WolffEngine(Engine):
    """Wolff cluster updates (paper S2): one "sweep" = one cluster flip."""

    name = "wolff"

    def from_full(self, full):
        return full

    def full_lattice(self, state):
        return state

    def scan_step(self, state, inv_temp, seed, step_count, n_sweeps):
        # cfg.temperature, not 1/inv_temp: the float32 round trip can land
        # 1 ulp off, which would fork the scan path from ``sweeps``; wolff
        # is key-based so it is never vmapped over an inv_temp batch
        key = jax.random.fold_in(jax.random.PRNGKey(seed), step_count)
        new, _ = wolff_mod.run_wolff(key, state,
                                     jnp.float32(self.cfg.temperature),
                                     n_sweeps)
        return new


    def state_arrays(self, state):
        return {"lattice": np.asarray(state)}

    def from_arrays(self, arrays):
        return jnp.asarray(arrays["lattice"])


# ---------------------------------------------------------------------------
# Edwards-Anderson spin-glass engine
# ---------------------------------------------------------------------------

@register
class SpinGlassEngine(Engine):
    """2D +-J Edwards-Anderson spin glass (paper S6's extension).

    State carries the quenched couplings alongside the lattice so a
    checkpoint restores the exact disorder realization.  Couplings are a
    pure function of the config seed (fold_in with a fixed tag), so two
    simulations with the same seed share a disorder sample.
    """

    name = "spinglass"
    param_fields = ("p_ferro",)

    _COUPLING_TAG = 0x51A55  # "glass": fold_in tag for the coupling stream

    def from_full(self, full):
        cfg = self.cfg
        ck = jax.random.fold_in(jax.random.PRNGKey(cfg.seed),
                                self._COUPLING_TAG)
        j_up, j_left = sg.init_couplings(ck, cfg.n, cfg.m,
                                         p_ferro=cfg.p_ferro)
        return (full, j_up, j_left)

    def full_lattice(self, state):
        return state[0]

    def magnetization(self, state):
        return state[0].astype(jnp.float32).mean()

    def observables(self, state, inv_temp):
        # energy must weight every bond by its quenched coupling; the
        # layout-generic full-lattice default would silently assume J=+1
        return {"m": obs.magnetization_full(state[0]),
                "e": sg.energy_per_spin(*state)}

    def scan_step(self, state, inv_temp, seed, step_count, n_sweeps):
        full, j_up, j_left = state
        key = jax.random.fold_in(jax.random.PRNGKey(seed), step_count)
        full, _ = sg.run_sweeps(full, j_up, j_left, inv_temp, key, n_sweeps)
        return (full, j_up, j_left)


    def state_arrays(self, state):
        return {"lattice": np.asarray(state[0]),
                "j_up": np.asarray(state[1]),
                "j_left": np.asarray(state[2])}

    def from_arrays(self, arrays):
        return (jnp.asarray(arrays["lattice"]),
                jnp.asarray(arrays["j_up"]),
                jnp.asarray(arrays["j_left"]))
