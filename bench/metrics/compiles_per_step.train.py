"""XLA compiles (persistent-cache loads included) during the window, per
step (``jax.monitoring``'s ``backend_compile_duration`` events)."""


def read(ctx):
    w = ctx.window
    return w.compiles / w.steps
