"""Share of the window's wall time the host spent blocked on the device
(span ``heta.device.wait``: the step's ``float(loss)``, which waits for the
transfers the staging enqueued and for the device step), summed over the
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
    return sum(s.seconds for s in spans
               if s.name == "heta.device.wait") / ctx.window.wall_s
