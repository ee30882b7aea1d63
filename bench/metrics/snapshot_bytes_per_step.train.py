"""Bytes of feature tables the table snapshot returns per step (counter
``heta.stage.snapshot_bytes`` of span ``heta.stage.snapshot``), over the
window's steps from the program's own recorder."""

from bench.harness import WARM_STEPS


def read(ctx):
    try:
        from repro import obs
    except ImportError:  # a program without the recorder
        return None
    spans = obs.window(WARM_STEPS, ctx.window.steps)
    if spans is None:
        return None
    return sum(s.counts.get("heta.stage.snapshot_bytes", 0) for s in spans
               if s.counts) / ctx.window.steps
