"""boundary_idle_share: 1 - the device's busy share over the traced
slice that opens at the first record of the burst that opens the
window: the cohort boundary, where the writer takes a finished cohort
and the driver admits the next (trace_reduce.py)."""


def read(ctx):
    if ctx.trace is None or ctx.trace.get("boundary") is None:
        return None
    return ctx.trace["boundary"]["idle_share"]
