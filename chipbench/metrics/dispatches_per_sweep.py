"""Compiled calls the program launched per sweep in the window: the
delta of its ``dispatches`` telemetry counter over the sweeps run."""


def read(ctx):
    if not ctx["sweeps"]:
        return None
    return ctx["dispatches"] / ctx["sweeps"]
