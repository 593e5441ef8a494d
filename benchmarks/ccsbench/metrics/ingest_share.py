"""ingest_share: seconds the ingest threads spent reading holes
(Metrics.t_ingest) in the window, over the window's seconds."""


def read(ctx):
    return ctx.delta("t_ingest") / ctx.window_s
