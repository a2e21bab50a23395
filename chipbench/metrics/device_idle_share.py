"""Share of the traced window in which no op ran on the device:
1 - busy union / window, averaged over the chips used, in %."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not trace["devices"] or not trace["window_ns"]:
        return None
    busy = [d["busy_ns"] for d in trace["devices"].values()]
    return 100.0 * (1.0 - sum(busy) / len(busy) / trace["window_ns"])
