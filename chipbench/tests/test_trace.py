"""The trace reduction (``chipbench/trace.py``) and the per-layer
readers: on synthetic events whose sums are known, and on two small
traces recorded on a TPU v5 lite (``data/``, trimmed from a traced run
of ``multispin.sweep`` and of ``multispin.measure``)."""
import json
from pathlib import Path

import pytest

from chipbench import run
from chipbench import trace as ctrace

DATA = Path(__file__).resolve().parent / "data"
TPU = "/device:TPU:0"
PLANE = "u32[32768,2048]{1,0:T(8,128)}"
KERNEL = (f"%closed_call.20 = {PLANE} custom-call(u32[3]{{0:T(128)S(1)}} "
          f"%a, {PLANE} %t, {PLANE} %o, {PLANE} %o), "
          'custom_call_target="tpu_custom_call", operand_layout_constraints='
          "{u32[3]{0}, u32[32768,2048]{1,0}, u32[32768,2048]{1,0}, "
          "u32[32768,2048]{1,0}}")
PLANE_BYTES = 32768 * 2048 * 4


def ev(name, start, dur):
    return {"name": name, "start_ns": float(start), "dur_ns": float(dur)}


def host(name, start, dur):
    return {"name": "chipbench." + name, "start_ns": float(start),
            "dur_ns": float(dur)}


# window [100, 1100): a while holding two kernels (the first cut by the
# window's start), a fusion overlapping the second kernel, XLA's own
# custom call, a collective, and an idle gap [700, 900) while the host
# waits
SYNTH = {
    "devices": {TPU: [
        ev("%while.3 = (s32[], u32[4]{0}) while((s32[], u32[4]{0}) %t), "
           "condition=%c, body=%b", 40, 520),
        ev(KERNEL, 50, 150),
        ev(KERNEL.replace("closed_call.20", "closed_call.21"), 300, 200),
        ev("%fusion.7 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop", 450, 100),
        ev('%custom-call.1 = f32[8]{0} custom-call(), '
           'custom_call_target="AllocateBuffer"', 560, 10),
        ev("%collective-permute-start.1 = (u32[1,8]{1,0}, u32[1,8]{1,0}) "
           "collective-permute-start(u32[1,8]{1,0} %r), "
           "source_target_pairs={{0,1},{1,0}}", 600, 100),
        ev("%fusion.8 = f32[]{:T(128)} fusion(f32[8]{0} %y)", 900, 250),
    ]},
    "host": [host("window", 100, 1000), host("wait", 650, 300),
             host("sweep", 100, 20)],
}


def test_kinds_by_opcode():
    kinds = [ctrace.kind(e) for e in SYNTH["devices"][TPU]]
    assert kinds == ["container", "kernel", "kernel", "other", "other",
                     "collective", "other"]


def test_busy_union_and_sums():
    r = ctrace.reduce(SYNTH)
    d = r["devices"][TPU]
    assert r["window_ns"] == 1000
    # busy: [100,560) + [560,570) + [600,700) + [900,1100)
    assert d["busy_ns"] == 460 + 10 + 100 + 200
    assert d["kernel_ns"] == 100 + 200
    assert d["collective_ns"] == 100
    assert d["other_ns"] == 770 - 400
    assert d["kernel_events"] == 2 and d["kernel_bytes_events"] == 2
    # 4 planes and 12 bytes each; the first kernel keeps 100 of 150 ns
    one = 4 * PLANE_BYTES + 12
    assert d["kernel_bytes"] == pytest.approx(one * 100 / 150 + one)


def test_idle_gaps_are_named_by_the_host_span():
    r = ctrace.reduce(SYNTH)
    gaps = sorted(r["idle_gaps"], key=lambda g: -g[1])
    assert gaps[0] == ("wait", 200)
    assert sum(g for _, g in r["idle_gaps"]) == 1000 - 770


def test_breakdown_lists_ops_not_containers():
    b = ctrace.breakdown(ctrace.reduce(SYNTH))
    names = [n for n, _ in b["device_ops"]]
    assert set(names[:2]) == {"fusion.8 fusion f32[]",
                              "closed_call.21 custom-call u32[32768,2048]"}
    assert not any("while" in n for n in names)
    assert b["device_ops"][0][1] == pytest.approx(200e-9)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


@pytest.mark.parametrize("text,nbytes", [
    (KERNEL, 4 * PLANE_BYTES + 12),
    ("%f = (s8[2,4]{1,0}, f32[]) fusion(bf16[3]{0} %x), kind=kLoop",
     8 + 4 + 6),
    ('%c = f32[8]{0} custom-call(), custom_call_target="AllocateBuffer"',
     None),
    ("fusion.3", None),
])
def test_hlo_bytes(text, nbytes):
    assert ctrace.hlo_bytes(text) == nbytes


def test_window_span_must_be_unique():
    bad = dict(SYNTH, host=[h for h in SYNTH["host"]
                            if h["name"] != ctrace.WINDOW_SPAN])
    with pytest.raises(ValueError):
        ctrace.reduce(bad)


def _ctx(reduced, flips, sweeps=8, dispatches=1):
    peaks = json.loads((run.BENCH_DIR / "peaks.json").read_text())
    return {"trace": reduced, "flips": flips, "sweeps": sweeps,
            "calls": 1, "dispatches": dispatches, "window_s": 1e-6,
            "peak": peaks["devices"]["TPU v5 lite"]}


def test_readers_on_synthetic_trace():
    read = run.load_cell("multispin.sweep")["readers"]
    ctx = _ctx(ctrace.reduce(SYNTH), flips=1000)
    assert read["kernel_ns_per_flip"](ctx) == pytest.approx(0.3)
    assert read["other_device_ns_per_flip"](ctx) == pytest.approx(0.37)
    assert read["device_idle_share"](ctx) == pytest.approx(23.0)
    assert read["dispatches_per_sweep"](ctx) == pytest.approx(0.125)
    one = 4 * PLANE_BYTES + 12
    share = read["hbm_share"](ctx)
    assert share == pytest.approx(
        100 * one * (1 + 100 / 150) / 300e-9 / 819e9)


def test_readers_find_nothing_without_kernels():
    quiet = {"devices": {TPU: [ev("%fusion.1 = f32[] fusion()", 100, 10)]},
             "host": [host("window", 100, 1000)]}
    read = run.load_cell("multispin.sweep")["readers"]
    ctx = _ctx(ctrace.reduce(quiet), flips=1000)
    assert read["kernel_ns_per_flip"](ctx) is None
    assert read["hbm_share"](ctx) is None


def _recorded(name):
    return json.loads((DATA / f"{name}.json").read_text())


@pytest.mark.parametrize("name", ["multispin.sweep", "multispin.measure"])
def test_recorded_trace_sums(name):
    trace = _recorded(name)
    r = ctrace.reduce(trace)
    d = r["devices"][TPU]
    lo, hi = ctrace.window(trace)
    evs = trace["devices"][TPU]
    kernels = [e for e in evs if ctrace.KERNEL_TARGET in e["name"]]
    # the multispin kernel's calls do not overlap: their clipped sum is
    # their union
    assert d["kernel_ns"] == pytest.approx(sum(
        min(e["start_ns"] + e["dur_ns"], hi) - max(e["start_ns"], lo)
        for e in kernels if e["start_ns"] < hi))
    assert d["kernel_events"] == len(kernels) > 0
    assert d["kernel_bytes_events"] == d["kernel_events"]
    # every call passes the thresholds (10 words), the key and offset
    # (3), its target plane and the source plane three times, and
    # returns one plane
    per_call = 5 * PLANE_BYTES + 4 * (3 + 10)
    full = [e for e in kernels if lo <= e["start_ns"]
            and e["start_ns"] + e["dur_ns"] <= hi]
    assert ctrace.hlo_bytes(full[0]["name"]) == per_call
    assert 0 < d["busy_ns"] <= r["window_ns"]
    assert d["other_ns"] == pytest.approx(
        d["busy_ns"] - d["kernel_ns"] - d["collective_ns"])


def test_recorded_measure_trace_is_mostly_observables():
    """In the measure cell, sampling m and e after every sweep took more
    device time than the kernel (the finding this cell exists for)."""
    d = ctrace.reduce(_recorded("multispin.measure"))["devices"][TPU]
    assert d["other_ns"] > d["kernel_ns"]
    d = ctrace.reduce(_recorded("multispin.sweep"))["devices"][TPU]
    assert d["kernel_ns"] > 0.9 * d["busy_ns"]


def test_collective_reader_takes_the_busiest_chip():
    """The reader of the four-chip cell left for a later PR (PERF.md)."""
    read = run._load_reader(run.BENCH_DIR / "metrics"
                            / "collective_ns_per_flip.py",
                            "collective_ns_per_flip")
    permute = dict(SYNTH["devices"][TPU][5], dur_ns=300.0)
    two = {"devices": {TPU: SYNTH["devices"][TPU],
                       "/device:TPU:1": [permute]},
           "host": SYNTH["host"]}
    assert read(_ctx(ctrace.reduce(two), flips=1000)) == pytest.approx(0.3)
    assert read(_ctx(ctrace.reduce(SYNTH), flips=1000)) == pytest.approx(0.1)
