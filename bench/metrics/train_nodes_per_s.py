"""Target (seed) nodes trained per second: the window's steps times the
batch size over the window's wall time, from the start of its
``Heta.fit(steps=k)`` to the end of its last step (``block_until_ready``)."""


def read(ctx):
    w = ctx.window
    return w.steps * w.batch / w.wall_s
