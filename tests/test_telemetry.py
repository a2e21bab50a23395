"""Telemetry subsystem: spans, counters, trace schema, CLI (DESIGN.md S12).

Covers the counter semantics contract (dispatches / sweeps / spin_flips /
philox_draws) across every engine family, span nesting and fencing, both
export formats, the schema validators (golden file + violation catalogue
+ property round-trips), the summarize/validate CLI, and the
``DISPATCH_COUNT`` deprecation shim.
"""
import glob
import io
import json
import os
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import pytest

import repro.telemetry as tel
from _hypothesis_compat import given, settings, st
from repro.analysis.measure import MeasurementPlan
from repro.api import EngineSpec, LatticeSpec, RunSpec, Session, SweepSpec
from repro.api import describe
from repro.kernels.resident import decision_attrs
from repro.telemetry.__main__ import _load, main as telemetry_cli
from repro.telemetry.metrics import MetricsRegistry, diff_counters
from repro.telemetry.schema import (TelemetryError, validate_event,
                                    validate_snapshot, validate_trace)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GOLDEN = os.path.join(REPO, "tests", "data", "trace_golden.json")


@pytest.fixture
def traced():
    """Tracing on with a clean event list; always off again afterwards."""
    tel.TRACER.clear()
    tel.enable()
    yield tel.TRACER
    tel.disable()
    tel.TRACER.clear()


def _counters():
    return tel.REGISTRY.snapshot()


# ---------------------------------------------------------------------------
# metrics primitives
# ---------------------------------------------------------------------------


def test_counter_monotone_and_rejects_negative():
    reg = MetricsRegistry()
    c = reg.counter("c")
    c.inc()
    c.inc(41)
    assert c.value == 42
    with pytest.raises(ValueError):
        c.inc(-1)
    assert c.value == 42


def test_gauge_set_and_rejects_nonfinite():
    reg = MetricsRegistry()
    g = reg.gauge("g")
    assert g.value is None
    g.set(2.5)
    assert g.value == 2.5
    for bad in (float("nan"), float("inf")):
        with pytest.raises(ValueError):
            g.set(bad)


def test_histogram_stats():
    reg = MetricsRegistry()
    h = reg.histogram("h")
    assert h.stats() == {"count": 0}
    for v in (1.0, 3.0, 2.0):
        h.observe(v)
    s = h.stats()
    assert s == {"count": 3, "sum": 6.0, "min": 1.0, "max": 3.0,
                 "mean": 2.0}


def test_registry_kind_collision_and_identity():
    reg = MetricsRegistry()
    c = reg.counter("x")
    assert reg.counter("x") is c
    with pytest.raises(ValueError):
        reg.gauge("x")
    with pytest.raises(ValueError):
        reg.histogram("x")


def test_registry_reset_zeroes_in_place():
    """reset() must zero the *existing* instruments, not replace them --
    module-held references like tel.DISPATCHES survive."""
    reg = MetricsRegistry()
    c, g, h = reg.counter("c"), reg.gauge("g"), reg.histogram("h")
    c.inc(5)
    g.set(1.0)
    h.observe(2.0)
    reg.reset()
    assert reg.counter("c") is c and c.value == 0
    assert reg.gauge("g") is g and g.value is None
    assert reg.histogram("h") is h and h.stats() == {"count": 0}


def test_snapshot_shape_and_diff_counters():
    reg = MetricsRegistry()
    reg.counter("a").inc(3)
    base = reg.snapshot()
    validate_snapshot(base)
    assert set(base) == {"counters", "gauges", "histograms"}
    assert base["gauges"] == {}  # unset gauges are omitted
    reg.counter("a").inc(4)
    reg.counter("b").inc(1)
    assert diff_counters(base, reg.snapshot()) == {"a": 4, "b": 1}


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------


def test_span_nesting_depth_and_close_order(traced):
    with tel.span("outer", tag="o"):
        with tel.span("inner"):
            pass
        tel.instant("mark", x=1)
    names = [e["name"] for e in traced.events]
    # spans append at close: child first, instant in the middle
    assert names == ["inner", "mark", "outer"]
    by_name = {e["name"]: e for e in traced.events}
    assert by_name["outer"]["depth"] == 0
    assert by_name["inner"]["depth"] == 1
    assert by_name["mark"]["kind"] == "instant"
    assert by_name["outer"]["args"] == {"tag": "o"}
    # child interval contained in the parent's
    o, i = by_name["outer"], by_name["inner"]
    assert o["ts_us"] <= i["ts_us"]
    assert i["ts_us"] + i["dur_us"] <= o["ts_us"] + o["dur_us"] + 1e-3


def test_span_attrs_normalized_and_set(traced):
    with tel.span("s", lattice=(16, 16)) as sp:
        sp.set(batch=2, obj=object())
    (e,) = traced.events
    assert e["args"]["lattice"] == [16, 16]
    assert e["args"]["batch"] == 2
    assert isinstance(e["args"]["obj"], str)  # stringified, not dropped
    assert sp.duration_ns is not None and sp.duration_ns >= 0


def test_span_error_attr(traced):
    with pytest.raises(RuntimeError):
        with tel.span("boom"):
            raise RuntimeError("x")
    (e,) = traced.events
    assert e["args"]["error"] is True


def test_disabled_tracing_is_inert():
    tel.TRACER.clear()
    assert not tel.enabled()
    with tel.span("ghost") as sp:
        sp.set(a=1)
        sp.fence(object())  # must NOT try to block_until_ready this
    assert sp is tel.NULL_SPAN and sp.duration_ns is None
    tel.instant("ghost")
    assert tel.TRACER.events == []


def test_span_feeds_timing_histogram(traced):
    before = tel.REGISTRY.histogram("span_ms.histspan").stats()["count"]
    with tel.span("histspan"):
        pass
    s = tel.REGISTRY.histogram("span_ms.histspan").stats()
    assert s["count"] == before + 1


# ---------------------------------------------------------------------------
# export round-trips
# ---------------------------------------------------------------------------


def test_export_chrome_and_jsonl_agree(tmp_path, traced):
    with tel.span("a", k=3):
        tel.instant("p", family="stencil")
    cj = str(tmp_path / "t.json")
    jl = str(tmp_path / "t.jsonl")
    tel.export(cj, meta={"who": "test"})
    tel.export(jl, meta={"who": "test"})
    chrome = json.load(open(cj))
    validate_trace(chrome)
    stream = _load(jl)  # JSONL re-rendered to the chrome shape
    validate_trace(stream)
    strip = lambda evs: [{k: e[k] for k in ("name", "ph", "ts", "args")}
                         for e in evs]
    assert strip(chrome["traceEvents"]) == strip(stream["traceEvents"])
    assert chrome["meta"]["who"] == stream["meta"]["who"] == "test"
    assert chrome["metrics"] == stream["metrics"]
    phs = {e["name"]: e["ph"] for e in chrome["traceEvents"]}
    assert phs == {"a": "X", "p": "i"}


# ---------------------------------------------------------------------------
# schema: golden file, violation catalogue, property round-trips
# ---------------------------------------------------------------------------


def test_golden_trace_validates():
    """The committed trace of the acceptance run::

        python -m repro run --n 16 --engine multispin --n-measure 3 \\
            --measure-every 2 --thermalize 2 --trace ...

    stays loadable forever: >= 5 span types, counters exactly matching
    the spec's sweep plan (thermalize 2 + 3 x every-2 = 8 sweeps, ONE
    fused dispatch, 8 x 256 site updates)."""
    doc = json.load(open(GOLDEN))
    validate_trace(doc)
    names = {e["name"] for e in doc["traceEvents"]}
    assert len(names) >= 5
    assert {"session.open", "session.measure", "measure_scan",
            "dispatch", "spec.validate"} <= names
    assert doc["metrics"]["counters"] == {
        "dispatches": 1, "sweeps": 8,
        "spin_flips": 2048, "philox_draws": 2048}
    spec = RunSpec.from_json(doc["meta"]["spec_json"])
    assert spec.engine.name == "multispin"
    assert spec.sweep.total_sweeps == 8
    # and the summarize renderer digests it
    buf = io.StringIO()
    from repro.telemetry.__main__ import summarize
    summarize(doc, out=buf)
    assert "dispatches" in buf.getvalue()


_BAD_SNAPSHOTS = [
    ("not-a-dict", []),
    ("unknown-key", {"counters": {}, "gauges": {}, "histograms": {},
                     "extra": {}}),
    ("missing-section", {"counters": {}, "gauges": {}}),
    ("negative-counter", {"counters": {"c": -1}, "gauges": {},
                          "histograms": {}}),
    ("bool-counter", {"counters": {"c": True}, "gauges": {},
                      "histograms": {}}),
    ("float-counter", {"counters": {"c": 1.5}, "gauges": {},
                       "histograms": {}}),
    ("nonfinite-gauge", {"counters": {}, "gauges": {"g": float("inf")},
                         "histograms": {}}),
    ("empty-name", {"counters": {"": 1}, "gauges": {},
                    "histograms": {}}),
    ("empty-hist-extra-keys", {"counters": {}, "gauges": {},
                               "histograms": {"h": {"count": 0,
                                                    "sum": 0.0}}}),
    ("hist-missing-mean", {"counters": {}, "gauges": {},
                           "histograms": {"h": {"count": 1, "sum": 1.0,
                                                "min": 1.0,
                                                "max": 1.0}}}),
    ("hist-order-violated", {"counters": {}, "gauges": {},
                             "histograms": {"h": {"count": 2, "sum": 3.0,
                                                  "min": 2.0, "max": 1.0,
                                                  "mean": 1.5}}}),
]


@pytest.mark.parametrize(
    "snap", [s for _, s in _BAD_SNAPSHOTS],
    ids=[n for n, _ in _BAD_SNAPSHOTS])
def test_snapshot_violations_rejected(snap):
    with pytest.raises(TelemetryError):
        validate_snapshot(snap)


def _ev(**over):
    ev = {"name": "s", "cat": "repro", "ph": "X", "ts": 1.0, "dur": 2.0,
          "pid": 0, "tid": 1, "args": {}}
    ev.update(over)
    return {k: v for k, v in ev.items() if v is not ...}


_BAD_EVENTS = [
    ("bad-ph", _ev(ph="B")),
    ("no-name", _ev(name="")),
    ("unknown-key", _ev(bogus=1)),
    ("complete-missing-dur", _ev(dur=...)),
    ("instant-with-dur", _ev(ph="i", s="t")),
    ("negative-ts", _ev(ts=-1.0)),
    ("nonfinite-dur", _ev(dur=float("nan"))),
    ("tid-not-int", _ev(tid="main")),
    ("args-nested-dict", _ev(args={"k": {"nested": 1}})),
    ("args-list-of-dicts", _ev(args={"k": [{"nested": 1}]})),
]


@pytest.mark.parametrize(
    "ev", [e for _, e in _BAD_EVENTS], ids=[n for n, _ in _BAD_EVENTS])
def test_event_violations_rejected(ev):
    with pytest.raises(TelemetryError):
        validate_event(ev)
    with pytest.raises(TelemetryError):
        validate_trace({"traceEvents": [ev]})


def test_trace_document_violations_rejected():
    with pytest.raises(TelemetryError):
        validate_trace([])
    with pytest.raises(TelemetryError):
        validate_trace({"traceEvents": [], "bogus": 1})
    with pytest.raises(TelemetryError):
        validate_trace({"traceEvents": {}})
    with pytest.raises(TelemetryError):
        validate_trace({"traceEvents": [], "meta": "not-a-dict"})
    with pytest.raises(TelemetryError):  # embedded snapshot validated too
        validate_trace({"traceEvents": [],
                        "metrics": {"counters": {"c": -1}, "gauges": {},
                                    "histograms": {}}})


@settings(max_examples=30)
@given(a=st.integers(min_value=0, max_value=2 ** 62),
       b=st.integers(min_value=0, max_value=2 ** 62),
       g=st.floats(min_value=-1e12, max_value=1e12))
def test_snapshot_roundtrip_property(a, b, g):
    reg = MetricsRegistry()
    reg.counter("a").inc(a)
    reg.counter("b").inc(b)
    reg.gauge("g").set(g)
    snap = reg.snapshot()
    validate_snapshot(snap)
    back = json.loads(json.dumps(snap))
    validate_snapshot(back)
    assert back["counters"] == {"a": a, "b": b}


@settings(max_examples=30)
@given(xs=st.tuples(st.floats(min_value=-1e6, max_value=1e6),
                    st.floats(min_value=-1e6, max_value=1e6),
                    st.floats(min_value=-1e6, max_value=1e6)))
def test_histogram_summary_property(xs):
    reg = MetricsRegistry()
    h = reg.histogram("h")
    for v in xs:
        h.observe(v)
    validate_snapshot(reg.snapshot())
    s = h.stats()
    assert s["min"] <= s["mean"] <= s["max"]
    assert s["count"] == len(xs)


@settings(max_examples=30)
@given(ts=st.floats(min_value=0.0, max_value=1e12),
       dur=st.floats(min_value=0.0, max_value=1e9),
       instant=st.booleans())
def test_event_roundtrip_property(ts, dur, instant):
    ev = {"name": "s", "cat": "repro", "ts": ts, "pid": 0, "tid": 7,
          "args": {"k": 1}}
    if instant:
        ev.update(ph="i", s="t")
    else:
        ev.update(ph="X", dur=dur)
    validate_trace(json.loads(json.dumps({"traceEvents": [ev]})))


# ---------------------------------------------------------------------------
# engine-family integration: counters + span nesting for Session.run
# ---------------------------------------------------------------------------

FAMILIES = [("stencil_pallas", {}), ("multispin", {}),
            ("bitplane", {}), ("tensorcore", {"tc_block": 4})]


@pytest.mark.parametrize("engine,params", FAMILIES,
                         ids=[f for f, _ in FAMILIES])
def test_session_run_counters_and_spans(engine, params, traced):
    spec = RunSpec(lattice=LatticeSpec(n=16, m=16),
                   engine=EngineSpec(name=engine, params=params),
                   temperature=2.0, seed=3)
    info = describe(spec)
    base = _counters()
    session = Session.open(spec)
    session.run(2)
    d = diff_counters(base, _counters())
    sites = 16 * 16
    assert d["dispatches"] == 1, engine
    assert d["sweeps"] == 2, engine  # lattice time, NOT x replicas
    assert d["spin_flips"] == 2 * sites * info["replicas"], engine
    assert d["philox_draws"] == \
        (2 * sites if info["counter_based"] else 0), engine

    by_name = {}
    for e in traced.events:
        by_name.setdefault(e["name"], []).append(e)
    assert {"session.open", "session.run", "dispatch"} <= set(by_name)
    dsp, run = by_name["dispatch"][-1], by_name["session.run"][-1]
    assert dsp["args"]["engine"] == engine
    assert dsp["args"]["k"] == 2
    assert dsp["args"]["lattice"] == [16, 16]
    # the dispatch interval nests inside session.run's
    assert run["ts_us"] <= dsp["ts_us"]
    assert dsp["ts_us"] + dsp["dur_us"] \
        <= run["ts_us"] + run["dur_us"] + 1e-3


def test_session_measure_counts_one_fused_dispatch(traced):
    spec = RunSpec(lattice=LatticeSpec(n=16, m=16),
                   engine=EngineSpec(name="multispin"),
                   temperature=2.2, seed=5,
                   sweep=SweepSpec(thermalize=4, measure_every=3,
                                   n_measure=5))
    base = _counters()
    session = Session.open(spec)
    session.measure()
    d = diff_counters(base, _counters())
    assert d["dispatches"] == 1  # the whole plan is ONE fused scan
    assert d["sweeps"] == spec.sweep.total_sweeps == 4 + 5 * 3
    names = {e["name"] for e in traced.events}
    assert {"session.measure", "measure_scan", "dispatch"} <= names
    scan = [e for e in traced.events if e["name"] == "measure_scan"][-1]
    assert scan["args"]["n_measure"] == 5
    assert scan["args"]["sweeps_between"] == 3
    assert scan["args"]["thermalize"] == 4
    assert scan["args"]["compile"] in ("first", "steady")


def test_planner_decision_instant_matches_dry_run(traced):
    """The planner.decide instant, describe()['resident'] (the --dry-run
    plan), and decision_attrs() are the same rendering -- a trace can
    never disagree with the printed plan."""
    spec = RunSpec(lattice=LatticeSpec(n=16, m=16),
                   engine=EngineSpec(name="stencil_pallas"),
                   temperature=2.0, seed=1)
    plan = describe(spec)
    decides = [e for e in traced.events
               if e["name"] == "planner.decide" and e["kind"] == "instant"]
    assert decides, "describe() must emit the planner.decide instant"
    assert decides[-1]["args"] == plan["resident"]
    assert plan["resident"] == decision_attrs("stencil", 16, 16)
    assert plan["resident"]["fits_vmem"] is True


# ---------------------------------------------------------------------------
# CLI: python -m repro run --trace / python -m repro.telemetry
# ---------------------------------------------------------------------------


def test_telemetry_cli_validate_rejects_malformed(tmp_path, capsys):
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"traceEvents": [{"name": "x"}]}))
    assert telemetry_cli(["validate", str(bad)]) == 1
    assert "INVALID" in capsys.readouterr().err
    notjson = tmp_path / "nope.jsonl"
    notjson.write_text("{malformed\n")
    assert telemetry_cli(["validate", str(notjson)]) == 1


def test_telemetry_cli_summarize_golden(capsys):
    assert telemetry_cli(["summarize", GOLDEN]) == 0
    out = capsys.readouterr().out
    assert "== spans ==" in out and "== counters ==" in out
    assert "measure_scan" in out and "dispatches" in out
    assert telemetry_cli(["validate", GOLDEN]) == 0


@pytest.mark.slow
def test_cli_traced_run_acceptance(tmp_path):
    """End-to-end acceptance: one traced CLI run produces a
    Perfetto-loadable trace with >= 5 span types whose counters match
    the spec's sweep plan exactly (fresh process => absolute totals)."""
    trace = str(tmp_path / "t.json")
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    subprocess.run(
        [sys.executable, "-m", "repro", "run", "--n", "16",
         "--engine", "multispin", "--n-measure", "3",
         "--measure-every", "2", "--thermalize", "2",
         "--trace", trace],
        check=True, env=env, timeout=600, cwd=str(tmp_path))
    doc = json.load(open(trace))
    validate_trace(doc)
    assert len({e["name"] for e in doc["traceEvents"]}) >= 5
    counters = doc["metrics"]["counters"]
    # the resilience counters register at import time and must all be
    # zero on a clean run (no retries/demotions/quarantines happened)
    recovery = {k: v for k, v in counters.items()
                if k.startswith(("resilience.", "resident.", "ckpt."))}
    assert all(v == 0 for v in recovery.values()), recovery
    # the compile account: the run's one program was traced, lowered
    # and compiled (or loaded) inside its spans
    compiles = {k: counters.pop(k)
                for k in ("compile_ns", "compile_cache_misses")}
    assert compiles["compile_ns"] > 0, compiles
    assert {k: v for k, v in counters.items()
            if k not in recovery} == {
        "dispatches": 1, "sweeps": 8,
        "spin_flips": 2048, "philox_draws": 2048,
        # unsharded run: the S15 halo counters exist but never fire
        "halo_exchanges": 0, "halo_bytes": 0}
    out = subprocess.run(
        [sys.executable, "-m", "repro.telemetry", "summarize", trace],
        check=True, env=env, timeout=120, capture_output=True, text=True)
    assert "dispatches" in out.stdout


# ---------------------------------------------------------------------------
# the profiler's clock and the compile account
# ---------------------------------------------------------------------------

SPEC16 = RunSpec(lattice=LatticeSpec(n=16, m=16),
                 engine=EngineSpec(name="multispin"), temperature=2.2,
                 seed=5)


def _host_events(profile_dir) -> set:
    """Names of the events on the host planes of a written profile."""
    (path,) = glob.glob(os.path.join(str(profile_dir), "**",
                                     "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    return {ev.name for plane in data.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events}


def test_spans_reach_the_profiler_host_plane(tmp_path):
    assert not tel.enabled()
    session = Session.open(SPEC16)
    session.run(2)  # compiled before the profile starts
    with jax.profiler.trace(str(tmp_path)):
        session.run(2)
        jax.block_until_ready(session.state)
    names = _host_events(tmp_path)
    assert {"repro.session.run", "repro.dispatch"} <= names, names


def test_cli_profile_holds_program_spans(tmp_path):
    """``python -m repro run --profile DIR``: one profile with the
    run's repro.* spans on its host plane."""
    prof = tmp_path / "prof"
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    subprocess.run(
        [sys.executable, "-m", "repro", "run", "--n", "16",
         "--engine", "multispin", "--sweeps", "2", "--profile", str(prof)],
        check=True, env=env, timeout=600, cwd=str(tmp_path))
    names = _host_events(prof)
    assert {"repro.session.open", "repro.session.run",
            "repro.dispatch"} <= names, names


@pytest.mark.parametrize("on", [False, True])
def test_disabled_spans_neither_fence_nor_record(on, monkeypatch):
    """Off, a span fences nothing and records no event.  On, the same
    calls fence and record (so the check can see both)."""
    fences = []
    real = jax.block_until_ready

    def counting(x):
        fences.append(1)
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", counting)
    tel.TRACER.clear()
    if on:
        tel.enable()
    try:
        session = Session.open(SPEC16)
        session.run(2)
        session.measure(MeasurementPlan(n_measure=2, sweeps_between=1))
    finally:
        tel.disable()
    names = tel.TRACER.span_names()
    tel.TRACER.clear()
    assert tel.TRACER.open_depth() == 0
    if on:
        assert fences and {"session.run", "measure_scan",
                           "measure.fetch"} <= set(names)
    else:
        assert fences == [] and names == []


def test_open_depth_counts_every_span_per_thread():
    assert not tel.enabled() and tel.TRACER.open_depth() == 0
    seen = []
    with tel.span("a"):
        with tel.span("b"):
            seen.append(tel.TRACER.open_depth())
            t = threading.Thread(
                target=lambda: seen.append(tel.TRACER.open_depth()))
            t.start()
            t.join(timeout=30)
        with pytest.raises(RuntimeError):
            with tel.span("c"):
                raise RuntimeError("x")
        seen.append(tel.TRACER.open_depth())
    assert not t.is_alive()
    assert seen == [2, 0, 1] and tel.TRACER.open_depth() == 0


def _fresh_program(c):
    """A function JAX has not traced before: each call makes its own."""
    return jax.jit(lambda x: x * c + 1)


def test_compile_account_counts_inside_spans_only():
    x = jnp.arange(7.0)
    ns = tel.COMPILE_NS.value
    _fresh_program(3.0)(x).block_until_ready()
    assert tel.COMPILE_NS.value == ns
    with tel.span("test.compile"):
        _fresh_program(3.0)(x).block_until_ready()
    assert tel.COMPILE_NS.value > ns
    ns = tel.COMPILE_NS.value
    with tel.span("test.compile"):
        f = _fresh_program(5.0)
        f(x).block_until_ready()
        grown = tel.COMPILE_NS.value - ns
        f(x).block_until_ready()  # cached: no more compile time
    assert tel.COMPILE_NS.value - ns == grown > 0


def test_compile_account_nested_trace_counts_once():
    """An event that contains earlier ones (a jit traced inside
    another's trace) adds only its own remainder."""
    event = "/jax/core/compile/jaxpr_trace_duration"
    account = tel._CompileAccount()  # none of this thread's real events
    ns = tel.COMPILE_NS.value
    with tel.span("test.compile"):
        account.on_duration(event, 1.0)   # inner, ends first
        account.on_duration(event, 3.0)   # outer, contains it
        account.on_duration(event, 1e-4)  # later, disjoint
        account.on_duration("/jax/other_duration", 5.0)
    assert tel.COMPILE_NS.value - ns == 3_000_000_000 + 100_000
    account.on_duration(event, 2.0)  # outside every span
    assert tel.COMPILE_NS.value - ns == 3_000_000_000 + 100_000


def test_compile_account_counts_cache_misses(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_enable_compilation_cache")
    saved = {k: getattr(jax.config, k) for k in keys}
    x = jnp.arange(9.0)
    try:
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        compilation_cache.reset_cache()
        misses = tel.COMPILE_CACHE_MISSES.value
        _fresh_program(7.0)(x).block_until_ready()
        assert tel.COMPILE_CACHE_MISSES.value == misses
        with tel.span("test.compile"):
            _fresh_program(11.0)(x).block_until_ready()
        assert tel.COMPILE_CACHE_MISSES.value == misses + 1
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


# ---------------------------------------------------------------------------
# deprecation shim
# ---------------------------------------------------------------------------


def test_dispatch_count_shim_warns_and_tracks_counter():
    from repro.analysis import measure as msr
    with pytest.warns(DeprecationWarning, match="DISPATCH_COUNT"):
        v = msr.DISPATCH_COUNT
    assert v == tel.DISPATCHES.value
    tel.DISPATCHES.inc(0)  # no-op, but the shim is live, not a copy
    with pytest.warns(DeprecationWarning):
        assert msr.DISPATCH_COUNT == tel.DISPATCHES.value
    with pytest.raises(AttributeError):
        msr.NO_SUCH_NAME
