"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

Two stages, so that the second can be checked on a small recorded trace:

1. :func:`events` reads the file with ``jax.profiler.ProfileData`` and
   keeps, for every TPU device plane, the events of its ``XLA Ops`` line,
   and
   from the host planes the spans the harness wrote
   (``jax.profiler.TraceAnnotation`` names starting with ``chipbench.``).
   The result is plain JSON.
2. :func:`reduce` clips the device events to the traced window, takes the
   busy union, and splits the busy time into Pallas kernels, collectives
   and the rest; it also names the longest idle gaps by the harness span
   that was open on the host at the time.

On a TPU the op line's events are named by their HLO instruction
(``%closed_call.20 = u32[32768,2048]{1,0} custom-call(u32[3]{0} ...),
custom_call_target="tpu_custom_call", ...``), read off a v5e trace by
hand; the events carry no stats.  The opcode classes an event: a Pallas
kernel is a ``custom-call`` to ``tpu_custom_call``, a collective one of :data:`COLLECTIVES`,
a ``while`` or ``call`` a container (its time is busy time, but the ops
inside it are what the breakdown lists), everything else "other" (XLA
fusions, copies, reductions).  A kernel's bytes are the shapes of its
result and operands in that text.
"""
from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Tuple

#: host spans the harness writes; the window span bounds the trace
HOST_PREFIX = "chipbench."
WINDOW_SPAN = "chipbench.window"

#: the line of a TPU device plane that holds one event per HLO op
OP_LINE = "XLA Ops"


def events(path: str) -> dict:
    """Stage 1: the device op events and harness spans of one trace."""
    import jax
    profile = jax.profiler.ProfileData.from_file(path)
    devices: Dict[str, List[dict]] = {}
    for plane in profile.planes:
        if not re.fullmatch(r"/device:TPU:\d+", plane.name):
            continue
        devices[plane.name] = [
            {"name": ev.name, "start_ns": float(ev.start_ns),
             "dur_ns": float(ev.duration_ns)}
            for ln in plane.lines if ln.name == OP_LINE
            for ev in ln.events]
    host = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            for ev in ln.events:
                if ev.name.startswith(HOST_PREFIX):
                    host.append({"name": ev.name,
                                 "start_ns": float(ev.start_ns),
                                 "dur_ns": float(ev.duration_ns)})
    return {"devices": devices, "host": host}


# -- stage 2 -----------------------------------------------------------------

#: HLO opcodes of collectives, and of ops that only hold other ops
COLLECTIVES = ("collective-permute", "collective-permute-start",
               "collective-permute-done", "all-reduce", "all-reduce-start",
               "all-reduce-done", "all-gather", "all-gather-start",
               "all-gather-done", "reduce-scatter", "all-to-all",
               "collective-broadcast", "send", "send-done", "recv",
               "recv-done")
CONTAINERS = ("while", "conditional", "call")
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'


def _closing(text: str, i: int) -> int:
    """Index just past the parenthesis that closes the one at ``i``."""
    depth = 0
    for j in range(i, len(text)):
        if text[j] == "(":
            depth += 1
        elif text[j] == ")":
            depth -= 1
            if depth == 0:
                return j + 1
    return len(text)


def parse_op(text: str) -> dict:
    """Split an op event's name, which the TPU trace gives as the HLO
    instruction (``%x = u32[8]{0} custom-call(u32[3]{0} %a, ...), ...``),
    into its short name, opcode, result shape and operand list.  A bare
    name (``fusion.3``) gives its opcode from the name alone."""
    m = re.match(r"\s*%?([\w.\-]+)\s*=\s*", text)
    if not m:
        return {"name": text, "opcode": re.sub(r"\.\d+$", "", text),
                "result": "", "operands": ""}
    rest = text[m.end():]
    cut = _closing(rest, 0) if rest.startswith("(") else rest.find(" ")
    cut = len(rest) if cut < 0 else cut
    result, tail = rest[:cut], rest[cut:].lstrip()
    op = re.match(r"([a-z][a-z0-9\-]*)\(", tail)
    if not op:
        return {"name": m.group(1), "opcode": "", "result": result,
                "operands": ""}
    operands = tail[op.end() - 1:_closing(tail, op.end() - 1)]
    return {"name": m.group(1), "opcode": op.group(1), "result": result,
            "operands": operands}


def kind(ev: dict) -> str:
    """``kernel`` (a custom call to ``tpu_custom_call``, which is what a
    Pallas kernel lowers to; XLA's own custom calls, such as
    ``AllocateBuffer``, are not kernels), ``collective``, ``container``
    (while, conditional, call: they hold the ops they run) or
    ``other``."""
    opcode = parse_op(ev["name"])["opcode"]
    if opcode == "custom-call" and KERNEL_TARGET in ev["name"]:
        return "kernel"
    if opcode in COLLECTIVES:
        return "collective"
    if opcode in CONTAINERS:
        return "container"
    return "other"


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float,
                                                                  float]]:
    """Merged, sorted (start, end) intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals: Iterable[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in intervals)


def _clip(ev: dict, lo: float, hi: float) -> Optional[Tuple[float, float]]:
    s = max(ev["start_ns"], lo)
    e = min(ev["start_ns"] + ev["dur_ns"], hi)
    return (s, e) if e > s else None


def window(trace: dict) -> Tuple[float, float]:
    """(start, end) of the harness's window span, in trace time."""
    spans = [h for h in trace["host"] if h["name"] == WINDOW_SPAN]
    if len(spans) != 1:
        raise ValueError(f"trace holds {len(spans)} {WINDOW_SPAN!r} "
                         "spans, want 1")
    s = spans[0]
    return s["start_ns"], s["start_ns"] + s["dur_ns"]


_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8}


def _shape_bytes(text: str) -> int:
    total = 0
    for dtype, dims in re.findall(
            r"\b(pred|[su](?:8|16|32|64)|bf16|f(?:16|32|64))\[([0-9,]*)\]",
            text):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def hlo_bytes(text: str):
    """Bytes of the results and operands an op's HLO text passes (each
    operand as often as it is passed; attributes such as layout
    constraints are not counted).  ``None`` when the text gives no
    operand shapes."""
    op = parse_op(text)
    if not op["operands"] or not _shape_bytes(op["operands"]):
        return None
    return _shape_bytes(op["result"]) + _shape_bytes(op["operands"])


def reduce(trace: dict) -> dict:
    """Stage 2: per-device busy, kernel, collective and other time inside
    the window, the ops that took most time, and the longest idle gaps.

    All times are in ns.  ``kernel_bytes`` sums :func:`hlo_bytes` over
    the kernel events whose text gives shapes; ``kernel_bytes_events``
    counts those events, so a reader can tell whether every kernel call
    was sized."""
    lo, hi = window(trace)
    per_device = {}
    ops: Dict[str, float] = {}
    gaps: List[Tuple[float, float, str]] = []
    for dev, evs in sorted(trace["devices"].items()):
        by_kind: Dict[str, list] = {"kernel": [], "collective": [],
                                    "container": [], "other": []}
        kbytes = 0
        ksized = 0
        kcount = 0
        for ev in evs:
            iv = _clip(ev, lo, hi)
            if iv is None:
                continue
            k = kind(ev)
            by_kind[k].append(iv)
            if k != "container":
                label = _label(ev["name"])
                ops[label] = ops.get(label, 0.0) + (iv[1] - iv[0])
            if k == "kernel":
                kcount += 1
                b = hlo_bytes(ev["name"])
                if b is not None:
                    # an event cut by the window's edge moves that share
                    whole = ev["dur_ns"] or 1.0
                    kbytes += b * (iv[1] - iv[0]) / whole
                    ksized += 1
        busy = union(iv for ivs in by_kind.values() for iv in ivs)
        kern = union(by_kind["kernel"])
        coll = union(by_kind["collective"])
        per_device[dev] = {
            "busy_ns": length(busy),
            "kernel_ns": length(kern),
            "collective_ns": length(coll),
            "other_ns": length(busy) - length(union(by_kind["kernel"]
                                                    + by_kind["collective"])),
            "kernel_events": kcount,
            "kernel_bytes": kbytes,
            "kernel_bytes_events": ksized,
        }
        edge = lo
        for s, e in busy + [(hi, hi)]:
            if s > edge:
                gaps.append((edge, s, dev))
            edge = max(edge, e)
    return {"window_ns": hi - lo, "devices": per_device, "ops": ops,
            "idle_gaps": [(_host_label(trace, s, e), e - s)
                          for s, e, _ in gaps]}


def _label(text: str) -> str:
    """``<name> <opcode> <result shape>`` of an op, without layouts."""
    op = parse_op(text)
    shape = re.sub(r"\{[^{}]*\}", "", op["result"])[:80]
    return " ".join(x for x in (op["name"], op["opcode"], shape) if x)


def _host_label(trace: dict, s: float, e: float) -> str:
    """The innermost harness span overlapping [s, e] most, or ``none``."""
    best, best_overlap = "none", 0.0
    for h in trace["host"]:
        if h["name"] == WINDOW_SPAN:
            continue
        ov = min(e, h["start_ns"] + h["dur_ns"]) - max(s, h["start_ns"])
        if ov > best_overlap:
            best, best_overlap = h["name"][len(HOST_PREFIX):], ov
    return best


def breakdown(reduced: dict, top: int = 10) -> dict:
    """The contract's ``breakdown``: the device ops that took most time
    (summed over devices) and the longest idle gaps, in seconds."""
    ops = sorted(reduced["ops"].items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(reduced["idle_gaps"], key=lambda g: -g[1])[:top]
    return {"device_ops": [[n, t / 1e9] for n, t in ops],
            "idle_gaps": [[n, t / 1e9] for n, t in gaps]}
