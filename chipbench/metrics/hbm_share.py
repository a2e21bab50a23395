"""Achieved HBM bandwidth of the Pallas sweep kernel, as a share of the
chip's peak (the memory side of its roofline).

Bytes: the operands and results of each kernel call, read from the
shapes the lowered call passes (an aliased operand counts as read and
written), summed over the calls in the traced window.  Time: the
kernels' device time.  Peak: chipbench/peaks.json.  Every operand
is read once per call, so the share cannot pass 100% whatever
implements the kernel.  Nothing is reported unless every kernel call in
the window was sized.  Each cell that lists this metric runs one kernel
family, the one its configuration's engine names."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not ctx.get("peak"):
        return None
    devs = list(trace["devices"].values())
    calls = sum(d["kernel_events"] for d in devs)
    sized = sum(d["kernel_bytes_events"] for d in devs)
    ns = sum(d["kernel_ns"] for d in devs)
    if not calls or sized != calls or not ns:
        return None
    nbytes = sum(d["kernel_bytes"] for d in devs)
    return 100.0 * nbytes / (ns * 1e-9) / ctx["peak"]["hbm_bytes_per_s"]
