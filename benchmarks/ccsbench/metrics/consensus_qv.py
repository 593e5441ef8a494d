"""consensus_qv: Phred of the window's base error share, -10 log10 of
the summed edit distances over the summed template lengths of the
window's holes (a hole due and not emitted counts its whole template as
errors), capped at 60."""

import reference


def read(ctx):
    if not ctx.bases:
        return None
    return reference.qv(ctx.errors, ctx.bases)
