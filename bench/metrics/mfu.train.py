"""Model operations per second of the window over the chips' peak, in %:
one forward and backward pass's operations per batch (``bench/flops.py``,
at the sampled fanout and each type's own width) times the window's steps,
over its wall time, over chips x peak (``bench/peaks.json``)."""

from bench.flops import train_step_flops


def read(ctx):
    if ctx.peaks is None:
        return None
    w = ctx.window
    rate = train_step_flops(ctx.setup, w.batch) * w.steps / w.wall_s
    return 100.0 * rate / (ctx.chips * ctx.peaks["flops_per_s"])
