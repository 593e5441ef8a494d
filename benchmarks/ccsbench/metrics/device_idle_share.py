"""device_idle_share: 1 - the union of the device's program intervals
over the traced window (trace_reduce.py), averaged over the chips."""


def read(ctx):
    return None if ctx.trace is None else ctx.trace["idle_share"]
