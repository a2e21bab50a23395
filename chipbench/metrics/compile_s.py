"""Seconds the program spent tracing, lowering and compiling its own
programs, or loading them from the persistent cache: the ``compile_ns``
counter of ``repro.telemetry`` over the whole run, in seconds.

The program counts only while one of its spans is open, so the
harness's hot start, probe and copies and the reference replay are left
out.  A compile inside the window fails the run, so on a correct run
this is the set-up's.  ``None`` where the program keeps no such
counter; reading never creates it."""

COUNTER = "compile_ns"


def read(ctx):
    try:
        import repro.telemetry as tel
    except ImportError:
        return None
    value = tel.REGISTRY.snapshot()["counters"].get(COUNTER)
    return None if value is None else value / 1e9
