"""Share of the window's wall time the training loop waited for its next
batch from the sample stream (span ``heta.pipeline.wait``), summed over
the window's steps from the program's own recorder: the host work the
overlap with the device step failed to hide.  Nothing is read where no
step drew from a stream."""

from bench.harness import WARM_STEPS


def read(ctx):
    try:
        from repro import obs
    except ImportError:  # a program without the recorder
        return None
    spans = obs.window(WARM_STEPS, ctx.window.steps)
    if spans is None:
        return None
    waits = [s.seconds for s in spans if s.name == "heta.pipeline.wait"]
    if not waits:
        return None
    return sum(waits) / ctx.window.wall_s
