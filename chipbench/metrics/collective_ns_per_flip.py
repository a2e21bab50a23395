"""Device time of the collective ops (the halo exchange) per attempted
flip, on the chip that spent most, over the traced window's flips."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not trace["devices"] or not ctx["flips"]:
        return None
    vals = [d["collective_ns"] for d in trace["devices"].values()]
    if not any(vals):
        return None
    return max(vals) / ctx["flips"]
