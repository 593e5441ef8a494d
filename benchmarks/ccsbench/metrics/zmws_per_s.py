"""zmws_per_s: consensus records the writer received in the window,
over the window's seconds."""


def read(ctx):
    return len(ctx.records) / ctx.window_s
