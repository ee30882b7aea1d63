"""A per-layer metric that a later change adds as a file: the window's
number of steps."""


def read(ctx):
    return ctx.window.steps
