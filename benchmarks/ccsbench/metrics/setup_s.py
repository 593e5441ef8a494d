"""setup_s: process start to the window's opening: imports, device
check, native build, input, compile or cache loads, and the ramp."""


def read(ctx):
    return ctx.setup_s
