"""Busy device time outside the Pallas sweep kernels and collectives,
per attempted flip: XLA glue, copies, the observables of a measure
call, or the whole jnp sweep where no kernel runs.  Averaged over the
chips used, over the traced window's flips."""

KEY = "other_ns"


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not trace["devices"] or not ctx["flips"]:
        return None
    vals = [d[KEY] for d in trace["devices"].values()]
    if not any(vals):
        return None
    return sum(vals) / len(vals) / ctx["flips"]
