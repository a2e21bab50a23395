"""Programs the program compiled because JAX's persistent compilation
cache did not hold them: the ``compile_cache_misses`` counter of
``repro.telemetry`` over the whole run, counted as ``compile_ns`` is
(only inside the program's spans).  ``None`` where the program keeps no
such counter; reading never creates it."""

COUNTER = "compile_cache_misses"


def read(ctx):
    try:
        import repro.telemetry as tel
    except ImportError:
        return None
    return tel.REGISTRY.snapshot()["counters"].get(COUNTER)
