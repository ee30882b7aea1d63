"""Share of the sampled edge slots that hold a neighbor: Σ counter
``heta.stage.valid_slots`` / Σ counter ``heta.stage.edge_slots`` over the
window's steps, both counted in span ``heta.stage.gather`` from the staged
masks, from the program's own recorder.  The rest is padding every
aggregation still computes.  Nothing is read where neither counter is."""

from bench.harness import WARM_STEPS


def read(ctx):
    try:
        from repro import obs
    except ImportError:  # a program without the recorder
        return None
    spans = obs.window(WARM_STEPS, ctx.window.steps)
    if spans is None:
        return None
    valid = slots = 0
    for s in spans:
        if s.counts:
            valid += s.counts.get("heta.stage.valid_slots", 0)
            slots += s.counts.get("heta.stage.edge_slots", 0)
    if not slots:
        return None
    return valid / slots
