"""dispatches_per_zmw: device dispatches in the window
(Metrics.device_dispatches) per consensus record emitted in it."""


def read(ctx):
    n = len(ctx.records)
    return ctx.delta("device_dispatches") / n if n else None
