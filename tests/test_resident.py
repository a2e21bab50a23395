"""Resident-sweep tier (DESIGN.md S9): bit-exactness vs the
per-half-sweep oracles at several k and lattice sizes, the VMEM planner
fallback boundary (both sides), and the registry/measurement routing."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bitplane as bp
from repro.core import lattice as lat
from repro.core import metropolis as metro
from repro.core import multispin as ms
from repro.core.sim import SimConfig, Simulation
from repro.kernels import resident
from repro.kernels.bitplane.resident import bitplane_sweeps_resident
from repro.kernels.multispin.resident import multispin_sweeps_resident
from repro.kernels.stencil.resident import stencil_sweeps_resident

SHAPES = [(16, 32), (32, 64)]
KS = [1, 3]
BETA = jnp.float32(1 / 2.2)


def _planes(n, m, key=0):
    full = lat.init_lattice(jax.random.PRNGKey(key), n, m)
    return lat.split_checkerboard(full)


# ---------------------------------------------------------------------------
# kernel-level bit-exactness: resident(k) == k x per-half-sweep oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,m", SHAPES)
@pytest.mark.parametrize("k", KS)
def test_stencil_resident_bitexact(n, m, k):
    b, w = _planes(n, m)
    out = stencil_sweeps_resident(b, w, BETA, n_sweeps=k, seed=9,
                                  start_offset=4, interpret=True)
    ref = metro.run_sweeps_philox(b, w, BETA, k, seed=9,
                                  start_offset=4)  # donates b, w
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(ref[0]))
    np.testing.assert_array_equal(np.asarray(out[1]), np.asarray(ref[1]))


@pytest.mark.parametrize("n,m", SHAPES)
@pytest.mark.parametrize("k", KS)
def test_multispin_resident_bitexact(n, m, k):
    bw, ww = ms.pack_lattice(*_planes(n, m, key=1))
    out = multispin_sweeps_resident(bw, ww, BETA, n_sweeps=k, seed=7,
                                    start_offset=2, interpret=True)
    ref = ms.run_sweeps_packed(bw, ww, BETA, k, seed=7,
                               start_offset=2)  # donates bw, ww
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(ref[0]))
    np.testing.assert_array_equal(np.asarray(out[1]), np.asarray(ref[1]))


@pytest.mark.parametrize("n,m", SHAPES)
@pytest.mark.parametrize("k", KS)
def test_bitplane_resident_bitexact(n, m, k):
    fulls = jnp.stack([lat.init_lattice(
        jax.random.fold_in(jax.random.PRNGKey(2), r), n, m)
        for r in range(bp.N_REPLICAS)])
    bw, ww = bp.pack_lattices(fulls)
    out = bitplane_sweeps_resident(bw, ww, BETA, n_sweeps=k, seed=5,
                                   start_offset=6, interpret=True)
    ref = bp.run_sweeps_bitplane(bw, ww, BETA, k, seed=5,
                                 start_offset=6)  # donates bw, ww
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(ref[0]))
    np.testing.assert_array_equal(np.asarray(out[1]), np.asarray(ref[1]))


def test_resident_64bit_seed_matches_oracle():
    """Full 64-bit python seeds reach both Philox key lanes (seed_keys)."""
    b, w = _planes(16, 32, key=3)
    big = (0xABCD << 32) | 0x1234
    out = stencil_sweeps_resident(b, w, BETA, n_sweeps=2, seed=big,
                                  interpret=True)
    ref = metro.run_sweeps_philox(b, w, BETA, 2, seed=big)
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(ref[0]))


# ---------------------------------------------------------------------------
# VMEM planner: fit decision and the fallback boundary
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", ["stencil", "multispin", "bitplane"])
def test_planner_boundary_both_sides(family):
    """max_square_lattice is the boundary: n fits, the next lattice the
    engine accepts falls back."""
    n = resident.max_square_lattice(family)
    step = resident._FAMILIES[family].lattice_step
    assert n > 0 and n % step == 0
    assert resident.plan_resident(family, n, n) is not None
    assert resident.plan_resident(family, n + step, n + step) is None
    # the plan carries the model numbers it was approved under
    plan = resident.plan_resident(family, n, n)
    assert plan.working_set_bytes <= plan.budget_bytes
    assert plan.plane_bytes == resident.plane_bytes(family, n, n)


@pytest.mark.parametrize("family", ["stencil", "multispin", "bitplane"])
def test_block_plan_heights_are_whole_tiles(family):
    """Per-half-sweep row blocks are whole dtype tiles (32 int8 rows, 8
    uint32 rows) that divide the plane, within the VMEM limit, for
    lattice widths 64 to 32768."""
    tile = resident._FAMILIES[family].tile_rows
    m = 64
    while m <= 32768:
        plan = resident.block_plan(family, m, m)
        assert plan.block_rows % tile == 0, (m, plan)
        assert m % plan.block_rows == 0, (m, plan)
        assert plan.working_set_bytes <= plan.vmem_limit_bytes
        assert plan.vmem_limit_bytes == resident.VMEM_LIMIT_BYTES
        m *= 2


def test_block_plan_rows_shrink_as_rows_widen():
    """Block height follows the row bytes: wider planes, shorter
    blocks (the pre-planner 256-row default ignored the width)."""
    rows = [resident.block_plan("stencil", 32768, m).block_rows
            for m in (1024, 8192, 32768)]
    assert rows == sorted(rows, reverse=True) and rows[0] > rows[-1]


def test_planner_rejects_unknown_family():
    with pytest.raises(ValueError, match="unknown resident family"):
        resident.plan_resident("nope", 16, 16)


@pytest.mark.parametrize("engine,family,fit_n,spill_n", [
    ("stencil_pallas", "stencil", 32, 64),
    ("bitplane_pallas", "bitplane", 16, 32),
])
def test_engine_fallback_boundary_bitexact(monkeypatch, engine, family,
                                           fit_n, spill_n):
    """A lattice on each side of the (budget-moved) fallback boundary:
    the fitting size routes resident, the spilling size falls back to
    the per-half-sweep kernels -- and BOTH produce the oracle
    trajectory, so the tier decision is unobservable in the physics."""
    # scale the modeled bytes per cell so the limit falls between them
    fit_ws = resident.working_set_bytes(family, fit_n, fit_n)
    spill_ws = resident.working_set_bytes(family, spill_n, spill_n)
    assert fit_ws < spill_ws
    per_cell = resident._FAMILIES[family].resident_bytes
    scaled = resident.VMEM_LIMIT_BYTES * per_cell // spill_ws + 1
    monkeypatch.setitem(resident._FAMILIES, family, dataclasses.replace(
        resident._FAMILIES[family], resident_bytes=scaled))

    oracle = {"stencil_pallas": "basic_philox",
              "bitplane_pallas": "bitplane"}[engine]
    for n, expect_resident in ((fit_n, True), (spill_n, False)):
        cfg = dict(n=n, m=n, temperature=2.2, seed=7)
        sim = Simulation(SimConfig(engine=engine, **cfg))
        assert (sim.engine.resident_plan is not None) == expect_resident, n
        ref = Simulation(SimConfig(engine=oracle, **cfg))
        sim.run(3)
        ref.run(3)
        np.testing.assert_array_equal(np.asarray(sim.full_lattice()),
                                      np.asarray(ref.full_lattice()),
                                      err_msg=f"n={n}")


# ---------------------------------------------------------------------------
# registry / measurement routing
# ---------------------------------------------------------------------------

def test_multispin_pallas_engine_matches_oracle_engine():
    cfg = dict(n=32, m=32, temperature=2.2, seed=7)
    a = Simulation(SimConfig(engine="multispin", **cfg))
    b = Simulation(SimConfig(engine="multispin_pallas", **cfg))
    assert b.engine.resident_plan is not None
    a.run(5)
    b.run(5)
    np.testing.assert_array_equal(np.asarray(a.full_lattice()),
                                  np.asarray(b.full_lattice()))


def test_measure_blocks_map_to_resident_dispatches():
    """measure_every-sized sweep blocks through measure_scan are
    bit-identical between the resident engine and its pure-jnp oracle:
    each interval is one k-sweep resident call (k = sweeps_between)."""
    from repro.analysis.measure import MeasurementPlan
    plan = MeasurementPlan(n_measure=4, sweeps_between=2, thermalize=2)
    cfg = dict(n=16, m=16, temperature=2.2, seed=7)
    res = Simulation(SimConfig(engine="multispin_pallas", **cfg))
    ref = Simulation(SimConfig(engine="multispin", **cfg))
    traj_res = res.measure(plan)
    traj_ref = ref.measure(plan)
    for f in plan.fields:
        np.testing.assert_array_equal(traj_res[f], traj_ref[f], err_msg=f)


def test_ensemble_vmaps_resident_tier():
    """Ensemble members vmapped through the resident kernel follow
    their Simulation trajectories exactly (DESIGN.md S3 contract)."""
    from repro.core.ensemble import Ensemble
    temps, seeds = [1.8, 2.5], [3, 4]
    ens = Ensemble(16, 16, temps, seeds, engine="multispin_pallas")
    assert ens.engine.resident_plan is not None
    ens.run(3)
    lattices = ens.full_lattices()
    for i, (temp, seed) in enumerate(zip(temps, seeds)):
        sim = Simulation(SimConfig(n=16, m=16, temperature=temp,
                                   seed=seed, engine="multispin_pallas"))
        sim.run(3)
        np.testing.assert_array_equal(np.asarray(sim.full_lattice()),
                                      lattices[i], err_msg=f"member {i}")


def test_zero_sweeps_noop_on_every_tier():
    """n_sweeps=0 routes to the fallback fori_loop (which no-ops), so
    the zero-sweep edge behaves identically on resident-capable and
    plain engines."""
    for engine in ("stencil_pallas", "multispin_pallas", "basic_philox"):
        sim = Simulation(SimConfig(n=16, m=16, temperature=2.2, seed=7,
                                   engine=engine))
        before = np.asarray(sim.full_lattice())
        sim.run(0)
        np.testing.assert_array_equal(
            before, np.asarray(sim.full_lattice()), err_msg=engine)


# ---------------------------------------------------------------------------
# H1.5: int8 neighbor sums leave flip decisions bit-identical
# ---------------------------------------------------------------------------

def test_int8_neighbor_sums_bitidentical_flips():
    b, w = _planes(32, 64, key=5)
    nn = metro.neighbor_sums(w, is_black=True)
    assert nn.dtype == jnp.int8
    # int32-widened reference of the same accept math
    u = jax.random.uniform(jax.random.PRNGKey(6), b.shape)
    out = metro.update_color(b, w, u, BETA, is_black=True)
    t32 = b.astype(jnp.int32)
    acc32 = jnp.exp(-2.0 * BETA * nn.astype(jnp.int32).astype(jnp.float32)
                    * t32.astype(jnp.float32))
    ref = jnp.where(u < acc32, -t32, t32).astype(b.dtype)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
