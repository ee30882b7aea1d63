"""The readers of the sample stream's wait and ready counter
(``prefetch_ready_share.train``, ``pipeline_wait_share.train``) on spans
and counters recorded by ``repro.obs``, and their silence where a program
recorded none: a serial training loop, or a window the ring lost."""

import time
from types import SimpleNamespace

import pytest

from bench import harness
from repro import obs

READY = "prefetch_ready_share.train"
WAIT = "pipeline_wait_share.train"


def _record(steps, ready=(), wait_s=0.0, stream=True):
    """One session's training steps ``0 .. WARM_STEPS + steps - 1``, each
    a ``heta.step``; with ``stream`` each also draws its batch in a
    ``heta.pipeline.wait`` of ``wait_s``, counting ``heta.pipeline.ready``
    at the steps in ``ready``.  Returns the window's context."""
    sid = obs.new_session()
    t0 = time.perf_counter()
    for s in range(harness.WARM_STEPS + steps):
        if stream:
            with obs.scope(s, sid):
                with obs.span("heta.pipeline.wait"):
                    time.sleep(wait_s)
                    if s in ready:
                        obs.count("heta.pipeline.ready")
        with obs.span(obs.STEP, step=s, session=sid):
            with obs.span("heta.sample"):
                pass
    wall = time.perf_counter() - t0
    return SimpleNamespace(window=SimpleNamespace(steps=steps, wall_s=wall))


def test_ready_share_counts_the_windows_ready_steps():
    w = harness.WARM_STEPS
    # a ready set-up step lies outside the window and is not read
    ctx = _record(4, ready={0, w, w + 2, w + 3})
    assert harness.reader(READY)(ctx) == pytest.approx(3 / 4)
    assert harness.reader(READY)(_record(5)) == 0.0


def test_wait_share_sums_the_windows_waits_over_its_wall():
    ctx = _record(3, wait_s=0.01)
    share = harness.reader(WAIT)(ctx)
    waits = [r.seconds for r in obs.window(harness.WARM_STEPS, 3)
             if r.name == "heta.pipeline.wait"]
    assert len(waits) == 3 and min(waits) >= 0.01
    assert share == pytest.approx(sum(waits) / ctx.window.wall_s)
    assert 0.0 < share < 1.0


@pytest.mark.parametrize("name", [READY, WAIT])
def test_nothing_is_read_without_a_stream_or_a_whole_window(name):
    read = harness.reader(name)
    assert read(_record(3, stream=False)) is None
    ctx = _record(3, ready={harness.WARM_STEPS})
    ctx.window.steps = 4  # a step the ring never held
    assert read(ctx) is None
