"""Measurement & analysis subsystem: fused scan contract + estimators."""
import json
import os
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import (MeasurementPlan, RunRecorder, Welford, binder,
                            binder_crossing, blocking_error, jackknife,
                            parse_derived, specific_heat, susceptibility,
                            tau_int)
from repro.core import multispin as ms
from repro.core import observables as obs
from repro.core.engine import ENGINES, Engine
from repro.core.ensemble import Ensemble
from repro.core.sim import SimConfig, Simulation

# ---------------------------------------------------------------------------
# fused scan: bit-identity with the legacy python loop, one dispatch
# ---------------------------------------------------------------------------


def _legacy_trajectory(sim, n_measure, sweeps_between, thermalize=0):
    """The pre-analysis-subsystem measurement loop: one dispatch and one
    host round-trip per sample."""
    if thermalize:
        sim.run(thermalize)
    out = np.empty(n_measure, np.float32)
    for i in range(n_measure):
        sim.run(sweeps_between)
        out[i] = sim.magnetization()
    return out


@pytest.mark.parametrize("engine", ["multispin", "basic_philox"])
def test_scan_trajectory_bitexact_vs_python_loop(engine):
    cfg = dict(n=16, m=16, temperature=2.2, seed=7, engine=engine)
    a = Simulation(SimConfig(**cfg))
    legacy = _legacy_trajectory(a, 12, 2, thermalize=4)
    b = Simulation(SimConfig(**cfg))
    scan = b.trajectory(12, 2, thermalize=4)
    np.testing.assert_array_equal(legacy, scan)
    # the final engine states agree too, so a checkpoint after a fused
    # measurement continues the identical Philox stream
    np.testing.assert_array_equal(np.asarray(a.full_lattice()),
                                  np.asarray(b.full_lattice()))
    assert a.step_count == b.step_count == 4 + 12 * 2


def test_scan_trajectory_is_one_dispatch():
    import repro.telemetry as tel
    sim = Simulation(SimConfig(n=16, m=16, temperature=2.0, seed=1,
                               engine="multispin"))
    before = tel.DISPATCHES.value
    sim.trajectory(32, 2, thermalize=8)
    assert tel.DISPATCHES.value - before == 1  # legacy: 33 dispatches


def test_measure_fields_and_step_accounting():
    sim = Simulation(SimConfig(n=16, m=16, temperature=2.0, seed=2,
                               engine="basic_philox"))
    plan = MeasurementPlan(n_measure=5, sweeps_between=3, thermalize=4)
    traj = sim.measure(plan)
    assert set(traj) == {"m", "e"}
    assert traj["m"].shape == traj["e"].shape == (5,)
    assert traj["m"].dtype == np.float32
    assert sim.step_count == plan.total_sweeps == 4 + 5 * 3


def test_ensemble_measure_matches_member_simulations():
    temps, seeds = [1.8, 2.5], [3, 4]
    ens = Ensemble(16, 16, temps, seeds, engine="multispin")
    traj = ens.trajectory(6, 2, thermalize=2)
    assert traj.shape == (6, 2)
    for i, (T, s) in enumerate(zip(temps, seeds)):
        sim = Simulation(SimConfig(n=16, m=16, temperature=T, seed=s,
                                   engine="multispin"))
        np.testing.assert_array_equal(sim.trajectory(6, 2, thermalize=2),
                                      traj[:, i], err_msg=f"member {i}")


def test_measurement_plan_validation():
    with pytest.raises(AssertionError):
        MeasurementPlan(0, 1)
    with pytest.raises(AssertionError):
        MeasurementPlan(1, 1, thermalize=-1)
    assert MeasurementPlan(1, 1, fields=["m"]).fields == ("m",)


# ---------------------------------------------------------------------------
# engine observables hook: energy correct for every state layout
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_observables_hook_energy_ground_state(engine):
    """All-up lattice: e = -2 for every uniform-J engine (each spin has 4
    aligned bonds counted once per pair); spinglass weights its quenched
    couplings instead, so e = -<J> over bonds.  Replicated engines
    (bitplane) return per-replica vectors; from_full broadcasts, so
    every replica must agree."""
    cfg = SimConfig(n=16, m=16, temperature=2.0, seed=5, engine=engine,
                    tc_block=4)
    sim = Simulation(cfg)
    state = sim.engine.from_full(jnp.ones((16, 16), jnp.int8))
    o = sim.engine.observables(state, jnp.float32(cfg.inv_temp))
    m = np.asarray(o["m"], np.float32)
    assert m.size == sim.engine.replicas
    assert (m == 1.0).all()
    if engine == "spinglass":
        _, j_up, j_left = state
        expect = -(np.asarray(j_up, np.float32).sum()
                   + np.asarray(j_left, np.float32).sum()) / 256.0
        assert float(o["e"]) == pytest.approx(expect)
    else:
        assert (np.asarray(o["e"], np.float32) == -2.0).all()


@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_sim_energy_routes_through_hook(engine):
    sim = Simulation(SimConfig(n=16, m=16, temperature=2.0, seed=6,
                               engine=engine, tc_block=4))
    sim.run(2)
    hook = np.asarray(sim.engine.observables(
        sim.state, jnp.float32(sim.config.inv_temp))["e"], np.float32)
    # scalar engines: exact identity; replicated engines: replica mean
    assert sim.energy() == pytest.approx(float(hook.mean()), rel=1e-6)
    # layout-independent oracle on the full-lattice view (replica 0 for
    # replicated engines -- full_lattice is the replica-0 view)
    if engine != "spinglass":
        full = sim.full_lattice()
        assert hook.reshape(-1)[0] == float(obs.energy_per_spin_full(full))


# ---------------------------------------------------------------------------
# multispin engines: m and e counted on the packed words
# ---------------------------------------------------------------------------

PACKED_SHAPES = [(8, 16), (16, 32), (64, 128), (24, 80), (2, 16)]
PATTERNS = ["random", "all_up", "all_down", "antiferro", "row_stripes",
            "column_stripes"]


def _pattern(kind, n, m, seed=0):
    rows, cols = np.indices((n, m))
    spins = {
        "random": lambda: np.random.default_rng(seed).integers(0, 2, (n, m)),
        "all_up": lambda: np.ones((n, m), int),
        "all_down": lambda: np.zeros((n, m), int),
        "antiferro": lambda: (rows + cols) % 2,
        "row_stripes": lambda: rows % 2,
        "column_stripes": lambda: cols % 2,
    }[kind]()
    return jnp.asarray(2 * spins - 1, jnp.int8)


def _full_path(full):
    return {"m": obs.magnetization_full(full),
            "e": obs.energy_per_spin_full(full)}


def _packed_engine(name, n, m):
    return ENGINES[name](SimConfig(n=n, m=m, temperature=2.0, engine=name))


@pytest.mark.parametrize("shape", PACKED_SHAPES, ids=lambda s: "x".join(
    map(str, s)))
@pytest.mark.parametrize("kind", PATTERNS)
def test_packed_observables_bitwise_equal_full_lattice(shape, kind):
    """Exact counts, rounded once: the packed path equals the
    full-lattice observables bit for bit, eagerly and under jit."""
    full = _pattern(kind, *shape)
    engine = _packed_engine("multispin", *shape)
    state = engine.from_full(full)
    beta = jnp.float32(0.5)
    for got, want in [(engine.observables(state, beta), _full_path(full)),
                      (jax.jit(engine.observables)(state, beta),
                       jax.jit(_full_path)(full))]:
        assert np.asarray(got["m"]) == np.asarray(want["m"])
        assert np.asarray(got["e"]) == np.asarray(want["e"])
    assert np.asarray(engine.magnetization(state)) \
        == np.asarray(obs.magnetization_full(full))
    assert np.asarray(engine.energy(state)) \
        == np.asarray(obs.energy_per_spin_full(full))
    expect = {"all_up": (1.0, -2.0), "all_down": (-1.0, -2.0),
              "antiferro": (0.0, 2.0), "row_stripes": (0.0, 0.0),
              "column_stripes": (0.0, 0.0)}.get(kind)
    if expect is not None and min(shape) > 2:
        got = engine.observables(state, beta)
        assert (float(got["m"]), float(got["e"])) == expect


@pytest.mark.parametrize("shape", [(16, 32), (24, 80)],
                         ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("view", ["observables", "magnetization"])
def test_packed_observables_under_vmap(shape, view):
    """The ensemble path maps the hook over a batch of states."""
    fulls = [_pattern("random", *shape, seed=s) for s in range(4)] + [
        _pattern(k, *shape) for k in ("all_up", "antiferro")]
    engine = _packed_engine("multispin_pallas", *shape)
    states = jax.tree.map(lambda *xs: jnp.stack(xs),
                          *[engine.from_full(f) for f in fulls])
    if view == "observables":
        got = jax.vmap(engine.observables, in_axes=(0, None))(
            states, jnp.float32(0.5))
        for i, full in enumerate(fulls):
            want = _full_path(full)
            assert np.asarray(got["m"][i]) == np.asarray(want["m"])
            assert np.asarray(got["e"][i]) == np.asarray(want["e"])
    else:
        got = jax.vmap(engine.magnetization)(states)
        want = [np.asarray(obs.magnetization_full(f)) for f in fulls]
        np.testing.assert_array_equal(np.asarray(got), np.stack(want))


@pytest.mark.parametrize("width", [32768, 65536])
@pytest.mark.parametrize("up,anti", [("none", "none"), ("all", "all"),
                                     ("all", "none"), ("none", "all")])
def test_packed_count_combination_at_extremes(width, up, anti):
    """P in {0, N} and U in {0, 2N} on chip-sized lattices (N = 2^30
    and 2^32): no int32 wrap, and m, e land exactly on +-1, +-2."""
    n = width
    p_row = {"none": 0, "all": width}[up]
    u_row = {"none": 0, "all": 2 * width}[anti]
    spins = ms.spin_sum_from_counts(jnp.full((n,), p_row, jnp.int32),
                                    width)
    bonds = ms.bond_sum_from_counts(jnp.full((n,), u_row, jnp.int32),
                                    width)
    n_spins = n * width
    assert float(spins) == 2 * p_row * n - n_spins
    assert float(bonds) == 2 * n_spins - 2 * u_row * n
    assert float(spins / float(n_spins)) == (1.0 if up == "all" else -1.0)
    assert float(-bonds / float(n_spins)) == (2.0 if anti == "all"
                                              else -2.0)


@pytest.mark.parametrize("seed", range(3))
def test_packed_count_combination_rounds_once(seed):
    """Random per-row counts of a 65536^2 lattice: each sum is float32
    of the exact integer total, rounded once."""
    width = n = 65536
    rng = np.random.default_rng(seed)
    up = rng.integers(0, width + 1, n)
    anti = rng.integers(0, 2 * width + 1, n)
    want_m = np.float32(int((2 * up - width).sum()))
    want_b = np.float32(-2 * int((anti - width).sum()))
    assert float(ms.spin_sum_from_counts(jnp.asarray(up, jnp.int32),
                                         width)) == want_m
    assert float(ms.bond_sum_from_counts(jnp.asarray(anti, jnp.int32),
                                         width)) == want_b


@pytest.mark.parametrize("rows,width", [(1 << 19, 16), (1 << 16, 1 << 29)])
def test_packed_count_combination_refuses_what_would_wrap(rows, width):
    with pytest.raises(ValueError, match="int32 limb sums"):
        ms.spin_sum_from_counts(jnp.zeros((rows,), jnp.int32), width)


_BIG = 1 << 26   # elements: a quarter of a 32768^2 lattice


def _large_f32_s8(text):
    """Shapes of f32 / 8-bit arrays of at least _BIG elements in
    StableHLO text."""
    found = []
    for dims, dtype in re.findall(r"tensor<((?:\d+x)+)(f32|i8|ui8)>",
                                  text):
        size = int(np.prod([int(d) for d in dims.rstrip("x").split("x")]))
        if size >= _BIG:
            found.append(f"{dims}{dtype}")
    return found


def _lowered(view, engine, n, hook=None):
    planes = tuple(jax.ShapeDtypeStruct((n, n // 16), jnp.uint32)
                   for _ in range(2))
    if view == "observables":
        fn = hook or engine.observables
        return jax.jit(fn).lower(planes,
                                 jax.ShapeDtypeStruct((), jnp.float32))
    return jax.jit(hook or engine.magnetization).lower(planes)


@pytest.mark.parametrize("view", ["observables", "magnetization"])
@pytest.mark.parametrize("n", [32768, 65536])
def test_packed_observables_build_no_lattice_sized_temporaries(n, view):
    """Lowered (not run) at chip sizes: the packed hook holds no float32
    or int8 array of 2^26 elements or more; at 65536^2, N = 2^32 fits no
    int32 anywhere on the way."""
    engine = _packed_engine("multispin_pallas", n, n)
    assert _large_f32_s8(_lowered(view, engine, n).as_text()) == []


@pytest.mark.parametrize("view", ["observables", "magnetization"])
def test_full_lattice_default_builds_lattice_sized_temporaries(view):
    """The control for the test above: the full-lattice default the
    multispin engines no longer take holds such arrays at 32768^2."""
    n = 32768
    engine = _packed_engine("multispin_pallas", n, n)
    default = {"observables": lambda s, b: Engine.observables(engine, s, b),
               "magnetization": lambda s: Engine.magnetization(engine, s)}
    assert _large_f32_s8(_lowered(view, engine, n, default[view]).as_text())


@pytest.mark.parametrize("engine,path", [("multispin", "packed"),
                                         ("multispin_pallas", "packed"),
                                         ("basic_philox", "full")])
def test_measure_scan_span_names_observables_path(engine, path):
    import repro.telemetry as tel
    tel.TRACER.clear()
    tel.enable()
    try:
        Simulation(SimConfig(n=16, m=16, temperature=2.0, seed=3,
                             engine=engine)).trajectory(2, 1)
        scans = [e for e in tel.TRACER.events if e["name"] == "measure_scan"]
    finally:
        tel.disable()
        tel.TRACER.clear()
    assert scans and scans[-1]["args"]["observables"] == path


_MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import numpy as np
    from repro.api import (EngineSpec, LatticeSpec, MeshSpec, RunSpec,
                           Session, SweepSpec)
    from repro.core import observables as obs

    out = {}
    for engine in ("multispin", "multispin_pallas"):
        def spec(mesh):
            return RunSpec(lattice=LatticeSpec(n=64, m=256),
                           engine=EngineSpec(engine), temperature=2.27,
                           seed=2147483659, mesh=mesh,
                           sweep=SweepSpec(thermalize=2, measure_every=1,
                                           n_measure=4))
        single = Session.open(spec(None))
        want = single.measure()
        sharded = Session.open(spec(MeshSpec((2, 2))))
        got = sharded.measure()
        full = sharded.full_lattice()
        out[engine] = {
            "devices": len(sharded._runner.state[0].sharding.device_set),
            "samples_equal": all(
                np.array_equal(got[k], want[k]) for k in ("m", "e")),
            "m_equal": sharded.magnetization()
                       == float(obs.magnetization_full(full)),
            "e_equal": sharded.energy()
                       == float(obs.energy_per_spin_full(full)),
        }
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def mesh_measure():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                     "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _MESH_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("engine", ["multispin", "multispin_pallas"])
def test_packed_observables_on_a_four_device_mesh(mesh_measure, engine):
    """Session.measure on a sharded 2x2 state calls the packed hook
    eagerly: the samples equal the single-device run's bit for bit, and
    the final m and e the full-lattice path's.  N is a power of two:
    otherwise the fused scan's division by the constant N compiles to a
    multiply by its reciprocal, which can differ from the eager division
    by one ulp on either path."""
    r = mesh_measure[engine]
    assert r["devices"] == 4
    assert r["samples_equal"]
    assert r["m_equal"] and r["e_equal"]


# ---------------------------------------------------------------------------
# estimators
# ---------------------------------------------------------------------------


def test_welford_matches_numpy_and_merges():
    rng = np.random.default_rng(0)
    x = rng.normal(2.0, 3.0, size=10_000)
    w = Welford().push(x[:3000])
    w.merge(Welford().push(x[3000:]))
    assert w.n == x.size
    assert w.mean == pytest.approx(x.mean(), rel=1e-12)
    assert w.var == pytest.approx(x.var(ddof=1), rel=1e-9)
    assert w.sq_mean == pytest.approx((x ** 2).mean(), rel=1e-12)
    assert w.quad_mean == pytest.approx((x ** 4).mean(), rel=1e-12)
    assert w.abs_mean == pytest.approx(np.abs(x).mean(), rel=1e-12)


def test_tau_int_recovers_ar1_autocorrelation():
    """AR(1) with coefficient phi has tau_int = (1 + phi) / (1 - phi)."""
    rng = np.random.default_rng(1)
    phi, n = 0.7, 200_000
    x = np.empty(n)
    x[0] = 0.0
    noise = rng.normal(size=n)
    for t in range(1, n):
        x[t] = phi * x[t - 1] + noise[t]
    expect = (1 + phi) / (1 - phi)   # ~5.67
    assert tau_int(x) == pytest.approx(expect, rel=0.15)
    # iid series: tau_int ~ 1
    assert tau_int(rng.normal(size=50_000)) == pytest.approx(1.0,
                                                             abs=0.15)


def test_jackknife_and_blocking_errors_shrink_as_sqrt_n():
    """On iid data both error bars track sigma/sqrt(N): averaging over
    independent realizations, err(16N)/err(N) ~ 1/4."""
    rng = np.random.default_rng(2)

    def mean_err(estimator, n, reps=30):
        return np.mean([estimator(rng.normal(size=n)) for _ in range(reps)])

    for est in (lambda s: jackknife(s)[1], blocking_error):
        e_small = mean_err(est, 1_000)
        e_big = mean_err(est, 16_000)
        assert e_small / e_big == pytest.approx(4.0, rel=0.25), est
    # and the absolute scale is sigma/sqrt(N)
    assert mean_err(lambda s: jackknife(s)[1], 4_000) == pytest.approx(
        1.0 / np.sqrt(4_000), rel=0.2)


def test_jackknife_mean_is_plain_mean():
    x = np.arange(100, dtype=np.float64)
    est, err = jackknife(x)
    assert est == pytest.approx(x.mean())
    assert err > 0


def test_chi_and_cv_nonnegative_on_simulation_data():
    sim = Simulation(SimConfig(n=16, m=16, temperature=2.3, seed=9,
                               engine="multispin"))
    traj = sim.measure(MeasurementPlan(64, 1, thermalize=50))
    chi = susceptibility(traj["m"], 2.3, 256)
    cv = specific_heat(traj["e"], 2.3, 256)
    assert chi >= 0.0 and cv >= 0.0
    assert np.isfinite(chi) and np.isfinite(cv)
    # adversarial inputs cannot push them negative either
    rng = np.random.default_rng(3)
    for _ in range(20):
        s = rng.normal(size=32)
        assert susceptibility(s, 2.0, 64) >= 0.0
        assert specific_heat(s, 2.0, 64) >= 0.0


def test_binder_limits_and_crossing():
    # ordered phase: constant |m| -> U = 2/3; gaussian m -> U = 0
    assert binder(np.full(500, 0.8)) == pytest.approx(2.0 / 3.0)
    rng = np.random.default_rng(4)
    assert binder(rng.normal(size=400_000)) == pytest.approx(0.0,
                                                             abs=0.02)
    t = [2.0, 2.2, 2.4, 2.6]
    assert binder_crossing(t, [0.60, 0.50, 0.40, 0.30],
                           [0.65, 0.55, 0.35, 0.20]) == pytest.approx(2.3)
    assert binder_crossing(t, [0.6, 0.5, 0.4, 0.3],
                           [0.7, 0.6, 0.5, 0.4]) is None


def test_binder_crossing_brackets_tc_on_ensemble_scan():
    """Small two-size Ensemble scan: the U_L crossing lands near the
    exact T_c = 2.269185 (the examples/figures.py physics gate at
    sub-smoke scale)."""
    temps = [2.0, 2.1, 2.2, 2.3, 2.4, 2.6]
    plan = MeasurementPlan(n_measure=150, sweeps_between=2,
                           thermalize=200)
    u = {}
    for k, L in enumerate((16, 32)):
        ens = Ensemble(n=L, m=L, temperatures=temps,
                       seeds=[41 + 100 * k + i for i in range(len(temps))],
                       engine="multispin", init_p_up=1.0)
        m = ens.measure(plan)["m"]
        u[L] = [binder(m[:, i]) for i in range(len(temps))]
    tc = binder_crossing(temps, u[16], u[32])
    assert tc is not None
    assert abs(tc - obs.T_CRITICAL) < 0.15, (tc, u)


# ---------------------------------------------------------------------------
# recorder
# ---------------------------------------------------------------------------


def test_recorder_csv_schema_and_json_roundtrip(tmp_path):
    rec = RunRecorder(meta={"stamp": "test"})
    rec.record("fig5_L16_T2.000", 12.5, m=0.91234567, m_err=0.0123,
               note="x")
    row = rec.format_row(rec.rows[0])
    assert row == "fig5_L16_T2.000,12.5,m=0.912346;m_err=0.0123;note=x"
    assert parse_derived(row.split(",", 2)[2]) == {
        "m": 0.912346, "m_err": 0.0123, "note": "x"}
    csv = rec.write_csv(str(tmp_path / "out.csv"))
    lines = open(csv).read().splitlines()
    assert lines[0] == "name,us_per_call,derived" and lines[1] == row
    jpath = rec.write_json(str(tmp_path) + "/")
    assert "BENCH_test.json" in jpath
    import json
    with open(jpath) as f:
        data = json.load(f)
    assert data["rows"][0]["name"] == "fig5_L16_T2.000"
    assert data["meta"]["stamp"] == "test"
