"""window_compiles: compile events, persistent-cache loads included,
that JAX's monitoring hook saw inside the window."""


def read(ctx):
    return len(ctx.compiles)
