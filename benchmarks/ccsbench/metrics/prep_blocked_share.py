"""prep_blocked_share: seconds the driver waited on prep with nothing
to dispatch (Metrics.t_prep_blocked) in the window, over its seconds."""


def read(ctx):
    return ctx.delta("t_prep_blocked") / ctx.window_s
