"""rows_per_zmw: real (hole, pass) rows dispatched in the window
(Metrics.dp_rows_real) per consensus record emitted in it: the pass
windows a record took, over all its window attempts and growths."""


def read(ctx):
    n = len(ctx.records)
    return ctx.delta("dp_rows_real") / n if n else None
