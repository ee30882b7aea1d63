"""Share of the window's wall time the host spent handing staged arrays to
the device (span ``heta.stage.put``: ``raf_spmd.stack_batch`` and
``shard_arrays``; the enqueue, not the transfer, which the device wait
holds), summed over the window's steps from the program's own recorder."""

from bench.harness import WARM_STEPS


def read(ctx):
    try:
        from repro import obs
    except ImportError:  # a program without the recorder
        return None
    spans = obs.window(WARM_STEPS, ctx.window.steps)
    if spans is None:
        return None
    return sum(s.seconds for s in spans
               if s.name == "heta.stage.put") / ctx.window.wall_s
