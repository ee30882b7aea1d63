"""Share of the batch's feature rows the device gathered from the resident
cache rather than the host from a table snapshot: Σ counter
``heta.stage.device_rows`` / Σ (``heta.stage.device_rows`` +
``heta.stage.host_rows``) over the window's steps, both counted in span
``heta.stage.gather``, from the program's own recorder.  Nothing is read
where neither counter is."""

from bench.harness import WARM_STEPS


def read(ctx):
    try:
        from repro import obs
    except ImportError:  # a program without the recorder
        return None
    spans = obs.window(WARM_STEPS, ctx.window.steps)
    if spans is None:
        return None
    device = host = 0
    for s in spans:
        if s.counts:
            device += s.counts.get("heta.stage.device_rows", 0)
            host += s.counts.get("heta.stage.host_rows", 0)
    if not device + host:
        return None
    return device / (device + host)
