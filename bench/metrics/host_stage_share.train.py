"""Share of the window's wall time the session spent sampling and staging
batches on the host (its own ``Heta.host_times``, summed over the window's
steps)."""


def read(ctx):
    w = ctx.window
    return w.host_s / w.wall_s
