"""Bytes of host arrays put on the device per step (counter
``heta.stage.put_bytes`` of span ``heta.stage.put``: the stacked batch
``stack_batch_host`` assembles), over the window's steps from the program's
own recorder."""

from bench.harness import WARM_STEPS


def read(ctx):
    try:
        from repro import obs
    except ImportError:  # a program without the recorder
        return None
    spans = obs.window(WARM_STEPS, ctx.window.steps)
    if spans is None:
        return None
    return sum(s.counts.get("heta.stage.put_bytes", 0) for s in spans
               if s.counts) / ctx.window.steps
