"""Seconds from the start of the process to the start of the window: the
dataset, the session's stages, compiling, and the three set-up steps."""


def read(ctx):
    return ctx.setup_s
