"""dp_row_fill: real (hole, pass) rows over slab rows dispatched in the
window (Metrics.dp_rows_real / dp_rows_dispatched)."""


def read(ctx):
    rows = ctx.delta("dp_rows_dispatched")
    return ctx.delta("dp_rows_real") / rows if rows else None
