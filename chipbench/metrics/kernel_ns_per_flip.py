"""Device time of the Pallas sweep kernel per attempted flip.

Sum of the kernel events' device durations inside the traced window
(averaged over the chips used), over the flips of that window.  A
kernel is a custom call to ``tpu_custom_call`` in the trace
(chipbench/trace.py).  Each cell that lists this metric runs one kernel
family, the one its configuration's engine names."""

KEY = "kernel_ns"


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not trace["devices"] or not ctx["flips"]:
        return None
    vals = [d[KEY] for d in trace["devices"].values()]
    if not any(vals):
        return None
    return sum(vals) / len(vals) / ctx["flips"]
