"""Share of the window's steps whose batch was already waiting when the
training loop asked for it: Σ counter ``heta.pipeline.ready`` (counted by
the sample stream's draw, in span ``heta.pipeline.wait``) / the window's
steps, from the program's own recorder.  Near 1 the device sets the pace,
near 0 the host does.  Nothing is read where no step drew from a stream."""

from bench.harness import WARM_STEPS


def read(ctx):
    try:
        from repro import obs
    except ImportError:  # a program without the recorder
        return None
    spans = obs.window(WARM_STEPS, ctx.window.steps)
    if spans is None:
        return None
    waits = [s for s in spans if s.name == "heta.pipeline.wait"]
    if not waits:
        return None
    return sum((s.counts or {}).get("heta.pipeline.ready", 0)
               for s in waits) / ctx.window.steps
