"""``repro.telemetry`` -- spans, counters, and trace export (DESIGN.md S12).

Zero-dependency observability for the whole execution stack.  Two
halves with different costs:

* **Metrics** (:data:`REGISTRY`) are *always on*: one locked integer
  add per compiled dispatch on the host path.  The canonical counters
  below are the repo's physical accounting -- every BENCH dispatch
  column and every test dispatch assertion reads them.
* **Spans** (:data:`TRACER`) are *opt-in* (``enable()`` /
  ``python -m repro run --trace out.json``): when disabled, a span is
  one ``if not enabled`` branch and fencing never happens, so JAX's
  async pipelining is preserved (<2% overhead budget, EXPERIMENTS.md).

Quickstart::

    import repro.telemetry as tel
    tel.enable()
    ... run things ...
    tel.export("trace.json")        # Chrome trace (Perfetto-loadable)
    tel.export("trace.jsonl")       # line-delimited stream
    print(tel.REGISTRY.snapshot())  # counters/gauges/histograms

Counter semantics (asserted in tests/test_telemetry.py):

* ``dispatches``   -- +1 per compiled-call invocation (one fused
  measure_scan = ONE dispatch, regardless of sweeps inside).
* ``sweeps``       -- lattice-time sweeps advanced, NOT multiplied by
  replicas or batch members (a bitplane sweep advances 32 replicas one
  sweep = 1 here).
* ``spin_flips``   -- update attempts: sweeps x sites x replicas x
  batch (the flips/ns numerator of the paper's Table 1).
* ``philox_draws`` -- uint32s drawn by counter-based engines:
  sweeps x sites x batch (one draw per site per sweep; multispin packs
  8 sites per word but draws 8 offsets/word, bitplane shares one draw
  across its 32 replicas -- both land on exactly sites draws/sweep).
* ``halo_exchanges`` -- halo exchange *events* on the sharded paths:
  the per-half-sweep distributed tier performs 2 per sweep, the
  sharded resident tier (DESIGN.md S15 double-halo) exactly one per k
  sweeps -- the counter IS the assertion of that claim
  (tests/test_dist.py).
* ``halo_bytes``     -- bytes moved across the mesh by those
  exchanges, summed over every shard.
* ``compile_ns``     -- nanoseconds JAX spent tracing, lowering to MLIR
  and backend-compiling (or loading from the persistent cache) while a
  span was open on the compiling thread; nested events count once.
* ``compile_cache_misses`` -- persistent compilation cache misses
  (programs compiled and written to the cache) under the same rule.

The compile account counts only inside spans, so work of a caller
outside the program (a benchmark's own programs, a reference replay)
is left out.
"""
from __future__ import annotations

import collections
import threading
import time

import jax

from .metrics import (REGISTRY, Counter, Gauge, Histogram,
                      MetricsRegistry, diff_counters)
from .schema import (TelemetryError, validate_event, validate_snapshot,
                     validate_trace)
from .trace import NULL_SPAN, TRACER, SpanHandle, Tracer

__all__ = [
    "TRACER", "REGISTRY", "Tracer", "MetricsRegistry",
    "Counter", "Gauge", "Histogram", "SpanHandle", "NULL_SPAN",
    "TelemetryError", "validate_snapshot", "validate_trace",
    "validate_event", "diff_counters",
    "DISPATCHES", "SWEEPS", "SPIN_FLIPS", "PHILOX_DRAWS",
    "HALO_EXCHANGES", "HALO_BYTES", "COMPILE_NS", "COMPILE_CACHE_MISSES",
    "enable", "disable", "enabled", "reset", "span", "instant",
    "record_dispatch", "record_halo_exchange", "export",
]

#: canonical counters -- module-held references survive REGISTRY.reset()
DISPATCHES = REGISTRY.counter("dispatches")
SWEEPS = REGISTRY.counter("sweeps")
SPIN_FLIPS = REGISTRY.counter("spin_flips")
PHILOX_DRAWS = REGISTRY.counter("philox_draws")
HALO_EXCHANGES = REGISTRY.counter("halo_exchanges")
HALO_BYTES = REGISTRY.counter("halo_bytes")
COMPILE_NS = REGISTRY.counter("compile_ns")
COMPILE_CACHE_MISSES = REGISTRY.counter("compile_cache_misses")

#: JAX's duration events of one program's trace, lowering and compile
COMPILE_EVENTS = frozenset((
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration"))
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


def enable() -> None:
    """Turn span tracing on (counters are always on)."""
    TRACER.enable()


def disable() -> None:
    TRACER.disable()


def enabled() -> bool:
    return TRACER.enabled


def reset() -> None:
    """Drop recorded events and zero every metric (test isolation /
    the start of a traced bench run), keeping instrument identity."""
    TRACER.clear()
    REGISTRY.reset()


#: module-level aliases so call sites read ``tel.span("dispatch", ...)``
span = TRACER.span
instant = TRACER.instant


def record_dispatch(*, n_sweeps: int, sites: int, replicas: int = 1,
                    batch: int = 1, counter_based: bool = False) -> None:
    """Account one compiled-call invocation into the canonical counters.

    Call this from the stateful host wrapper that launches the compiled
    function -- NEVER from inside traced code (a jit trace would run the
    increment once, at trace time).
    """
    if n_sweeps < 0:
        raise ValueError(f"record_dispatch: n_sweeps={n_sweeps}")
    draws = int(n_sweeps) * int(sites)
    # all instruments share the registry lock: batch the adds into one
    # acquisition -- this sits on every dispatch path, so the disabled-
    # telemetry overhead budget (<2%, EXPERIMENTS.md) is set right here
    with REGISTRY._lock:
        DISPATCHES._value += 1
        SWEEPS._value += int(n_sweeps)
        SPIN_FLIPS._value += draws * int(replicas) * int(batch)
        if counter_based:
            PHILOX_DRAWS._value += draws * int(batch)


def record_halo_exchange(exchanges: int, bytes_moved: int) -> None:
    """Account halo traffic of one sharded dispatch: ``exchanges``
    exchange events moving ``bytes_moved`` bytes total (all shards,
    both planes).  Host-side only, like :func:`record_dispatch` --
    never call from traced code."""
    if exchanges < 0 or bytes_moved < 0:
        raise ValueError(
            f"record_halo_exchange: {exchanges=}, {bytes_moved=}")
    with REGISTRY._lock:
        HALO_EXCHANGES._value += int(exchanges)
        HALO_BYTES._value += int(bytes_moved)


def export(path: str, meta: dict | None = None) -> str:
    """Validate and write the current trace + metrics snapshot.

    ``*.jsonl`` -> line-delimited stream; anything else -> Chrome
    trace-event JSON (open in Perfetto / ``chrome://tracing``).
    """
    snap = REGISTRY.snapshot()
    validate_snapshot(snap)
    if path.endswith(".jsonl"):
        return TRACER.export_jsonl(path, metrics=snap, meta=meta)
    validate_trace(TRACER.to_chrome(metrics=snap, meta=meta))
    return TRACER.export_chrome(path, metrics=snap, meta=meta)


class _CompileAccount:
    """The ``jax.monitoring`` listeners behind :data:`COMPILE_NS` and
    :data:`COMPILE_CACHE_MISSES`.

    A duration event arrives when it ends.  Events nest on a thread (a
    jit traced inside another's trace), so each thread keeps the
    ``(start, duration)`` of the last events it counted; a new event
    that starts before them contains them, and only its own remainder
    is added."""

    #: counted events kept per thread (an event nesting more is rare)
    KEEP = 256

    def __init__(self):
        self._tls = threading.local()

    def on_duration(self, event: str, duration_secs: float,
                    **kwargs) -> None:
        if event not in COMPILE_EVENTS or not TRACER.open_depth():
            return
        dur = int(duration_secs * 1e9)
        start = time.perf_counter_ns() - dur
        done = getattr(self._tls, "done", None)
        if done is None:
            done = self._tls.done = collections.deque(maxlen=self.KEEP)
        inner = 0
        while done and done[-1][0] >= start:
            inner += done.pop()[1]
        done.append((start, dur))
        COMPILE_NS.inc(max(dur - inner, 0))

    def on_event(self, event: str, **kwargs) -> None:
        if event == CACHE_MISS_EVENT and TRACER.open_depth():
            COMPILE_CACHE_MISSES.inc()


_COMPILES = _CompileAccount()
jax.monitoring.register_event_duration_secs_listener(_COMPILES.on_duration)
jax.monitoring.register_event_listener(_COMPILES.on_event)
