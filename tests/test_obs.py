"""The span and counter recorder (``repro.obs``) and the session's books
that read it."""

import glob
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.api import (CacheConfig, DataConfig, Heta, HetaConfig,
                       ModelConfig, PartitionConfig, RunConfig)
from repro.data.sample_stream import SampleStream


def _mine(session):
    return [r for r in obs.records() if r.session == session]


def _tiny(**pipeline):
    cfg = HetaConfig(
        data=DataConfig(dataset="ogbn-mag", scale=0.002, fanouts=(3, 2),
                        batch_size=16),
        partition=PartitionConfig(num_partitions=2),
        model=ModelConfig(hidden=32, train_learnable=False),
        cache=CacheConfig(cache_mb=2),
        run=RunConfig(executor="raf_spmd", steps=3, lr=1e-2, seed=0),
    )
    return cfg.updated(pipeline=pipeline) if pipeline else cfg


def test_nesting_parent_links_and_self_time():
    sid = obs.new_session()
    with obs.span("outer", step=4, session=sid) as outer:
        time.sleep(0.002)
        with obs.span("inner") as inner:
            time.sleep(0.003)
            with obs.span("leaf") as leaf:
                time.sleep(0.001)
        with obs.span("inner") as inner2:
            pass
    recs = {id(r): r for r in _mine(sid)}
    assert set(recs) == {id(outer), id(inner), id(leaf), id(inner2)}
    assert outer.parent is None
    assert inner.parent is outer and inner2.parent is outer
    assert leaf.parent is inner
    assert outer.t0 <= inner.t0 <= leaf.t0 <= leaf.t1 <= inner.t1 <= outer.t1
    assert inner.self_seconds == pytest.approx(inner.seconds - leaf.seconds)
    assert outer.self_seconds == pytest.approx(
        outer.seconds - inner.seconds - inner2.seconds)
    assert outer.self_seconds >= 0.002 and leaf.seconds >= 0.001
    # the records close innermost first
    order = [r for r in _mine(sid)]
    assert order == [leaf, inner, inner2, outer]
    tot = obs.totals(sid)
    assert tot["inner"]["count"] == 2
    assert tot["inner"]["seconds"] == pytest.approx(inner.seconds + inner2.seconds)
    assert tot["inner"]["self_seconds"] == pytest.approx(
        inner.self_seconds + inner2.self_seconds)
    assert tot["outer"]["self_seconds"] == pytest.approx(outer.self_seconds)


def test_steps_and_sessions_are_inherited_and_given_ones_win():
    sid = obs.new_session()
    with obs.span("root", step=9, session=sid):
        with obs.span("a") as a:
            with obs.span("b", step=10) as b:
                with obs.span("c") as c:
                    pass
    assert (a.step, a.session) == (9, sid)
    assert (b.step, c.step, c.session) == (10, 10, sid)
    with obs.span("loose") as loose:
        pass
    assert loose.step is None and loose.session is None and loose.parent is None


def test_scope_binds_step_records_nothing_and_sums_its_children():
    sid = obs.new_session()
    with obs.span("around", session=sid) as around:
        with obs.scope(3, sid) as sc:
            with obs.span("x") as x:
                time.sleep(0.001)
            with obs.span("y") as y:
                pass
    assert [r.name for r in _mine(sid)] == ["x", "y", "around"]
    assert x.step == y.step == 3 and x.parent is around
    assert sc.child_s == pytest.approx(x.seconds + y.seconds)
    assert around.self_seconds == pytest.approx(
        around.seconds - x.seconds - y.seconds)


def test_steps_cross_the_sample_stream_producer_thread():
    """The stream's producer thread opens no step of its own: its spans take
    the item's global step ``first_step + i`` and the caller's session, and
    the item's host seconds are the summed spans."""
    sid = obs.new_session()
    producer = []

    def make(i):
        producer.append(threading.current_thread())
        with obs.span("heta.sample"):
            return i

    def stage(b):
        with obs.span("heta.stage"):
            time.sleep(0.001)
            return b * 10

    with SampleStream(make, stage, num_steps=4, depth=2, first_step=20,
                      session=sid) as stream:
        items = list(stream)
    assert [a for _, a, _ in items] == [0, 10, 20, 30]
    assert all(t is not threading.current_thread() for t in producer)
    recs = _mine(sid)
    for name in ("heta.sample", "heta.stage", "heta.pipeline.wait"):
        assert sorted(r.step for r in recs if r.name == name) == [20, 21, 22, 23]
    for b, _, host_s in items:
        spans = [r for r in recs if r.step == 20 + b
                 and r.name in ("heta.sample", "heta.stage")]
        assert host_s == pytest.approx(sum(r.seconds for r in spans))


def test_the_ring_drops_old_records_and_keeps_totals():
    sid = obs.new_session()
    for i in range(obs.RING + 10):
        with obs.span("heta.step", step=i, session=sid):
            pass
    ring = obs.records()
    assert len(ring) == obs.RING
    assert ring[-1].step == obs.RING + 9 and ring[0].step == 10
    assert obs.totals(sid)["heta.step"]["count"] == obs.RING + 10
    assert obs.window(10, 5, sid) is not None
    assert obs.window(9, 5, sid) is None  # step 9 was dropped
    assert obs.window(obs.RING + 5, 6, sid) is None  # step RING+10 never ran


def test_counters_go_to_the_innermost_open_span():
    sid = obs.new_session()
    with obs.span("heta.step", step=2, session=sid) as root:
        obs.count("things", 3)
        with obs.span("inner") as inner:
            obs.count("bytes", 100)
            obs.count("bytes", 28)
        obs.count("things")
    assert root.counts == {"things": 4}
    assert inner.counts == {"bytes": 128} and inner.step == 2
    assert obs.counters(sid) == {"bytes": 128, "things": 4}


def test_compiles_count_against_the_open_span_and_step():
    sid = obs.new_session()  # starts the listener
    x = jnp.arange(7.0)
    with obs.span("heta.step", step=5, session=sid):
        with obs.span("heta.device") as dev:
            # a fresh lambda: a new program, compiled here
            jax.jit(lambda v: v * 3.0 + 1.0)(x).block_until_ready()
    assert dev.counts["heta.compiles"] >= 1 and dev.step == 5
    assert obs.counters(sid)["heta.compiles"] == dev.counts["heta.compiles"]


def _step_books(sess, recs):
    """Per step of ``sess``: (heta.step's time before heta.device, the
    heta.device span's duration)."""
    roots = {r.step: r for r in recs if r.name == obs.STEP}
    devs = {r.step: r for r in recs if r.name == obs.DEVICE}
    assert sorted(roots) == sorted(devs) == list(range(len(sess.step_times)))
    for s, dev in devs.items():
        assert dev.parent is roots[s]
    return ([devs[s].t0 - roots[s].t0 for s in sorted(roots)],
            [devs[s].seconds for s in sorted(roots)])


def test_session_books_are_span_durations_on_the_serial_path():
    """``Heta.step`` is the serial surface: each step samples, stages and
    runs the device step inside its own ``heta.step``."""
    sess = Heta(_tiny())
    sess.build_graph()
    sess.partition()
    sess.profile_and_cache()
    sess.compile()
    for _ in range(3):
        sess.step()
    m = sess.results()
    recs = _mine(sess.obs_session)
    host, step = _step_books(sess, recs)
    assert sess.host_times == host
    assert sess.step_times == step
    setup = {r.name: r.seconds for r in recs if r.name.startswith("heta.setup.")}
    assert setup == {f"heta.setup.{k}": v for k, v in sess.stage_times.items()}
    assert set(sess.stage_times) == {"build_graph", "partition",
                                     "profile_and_cache", "compile"}
    # every span of a step lies inside its root, and the host work of the
    # step (sampling, staging) inside the time before the device step
    for r in recs:
        if r.step is not None and r.name != obs.STEP and r.parent is not None:
            p = r.parent
            assert p.t0 <= r.t0 <= r.t1 <= p.t1
    for s in range(3):
        inside = [r for r in recs if r.step == s and r.parent is not None
                  and r.parent.name == obs.STEP and r.name != obs.DEVICE]
        assert {r.name for r in inside} == {"heta.sample", "heta.stage"}
        assert sum(r.seconds for r in inside) <= sess.host_times[s]
    assert m["spans"]["heta.step"]["count"] == 3
    assert m["spans"]["heta.device.wait"]["count"] == 3
    snap = sess.engine.tables_snapshot()
    assert m["counters"]["heta.stage.snapshot_bytes"] == 3 * sum(
        t.nbytes for t in snap.values())


def _stream_books(**pipeline):
    """The session's books after ``fit(steps=4)``: the device step's span
    carries the step; the host time is the stream's, the summed sampling
    and staging spans of the item."""
    sess = Heta(_tiny(**pipeline))
    sess.build_graph()
    sess.partition()
    sess.profile_and_cache()
    sess.compile()
    sess.fit(steps=4)
    recs = _mine(sess.obs_session)
    _, step = _step_books(sess, recs)
    assert sess.step_times == step
    for s in range(4):
        host = [r for r in recs if r.step == s
                and r.name in ("heta.sample", "heta.stage")]
        assert sorted(r.name for r in host) == ["heta.sample", "heta.stage"]
        assert sess.host_times[s] == pytest.approx(sum(r.seconds for r in host))
    return sess


@pytest.mark.parametrize("snapshot", ["fresh", "stale"])
def test_session_books_of_fit_are_the_streams(snapshot):
    """``fit`` runs the thread stream under either snapshot policy (frozen
    rows: the producer stages in both), and ``results`` reports no sampler
    worker."""
    sess = _stream_books(snapshot=snapshot)
    m = sess.results()
    assert m["sampler_workers"] == 0
    assert m["spans"]["heta.pipeline.wait"]["count"] == 4


def _ready_counts(sess):
    """Per ``heta.pipeline.wait`` span of ``sess``: (step, its ready count)."""
    return [(r.step, (r.counts or {}).get("heta.pipeline.ready", 0))
            for r in _mine(sess.obs_session) if r.name == "heta.pipeline.wait"]


@pytest.mark.parametrize("slow", ["neither", "producer", "consumer"])
def test_pipeline_ready_counts_the_items_the_consumer_did_not_wait_for(slow):
    """``heta.pipeline.ready`` counts 1, in the wait span of the item's
    step, where the item was already in the queue: never when sampling is
    slower than the step, and after the first item when the step is."""
    sess = Heta(_tiny())
    sess.build_graph()
    sess.partition()
    sess.profile_and_cache()
    sess.compile()
    sess.fit(steps=2)  # compiles the step (twice over its first two)
    if slow == "producer":
        sample = sess._batch_for_step

        def late(s):
            time.sleep(0.3)
            return sample(s)

        sess._batch_for_step = late
    elif slow == "consumer":
        step = sess.executor.step_staged

        def slow_step(*args):
            time.sleep(0.3)
            return step(*args)

        sess.executor.step_staged = slow_step
    sess.fit(steps=4)
    counts = _ready_counts(sess)
    assert [s for s, _ in counts] == list(range(6))
    assert all(n in (0, 1) for _, n in counts)
    ready = sum(n for s, n in counts if s >= 2)
    assert obs.counters(sess.obs_session).get("heta.pipeline.ready", 0) == sum(
        n for _, n in counts)
    assert ready <= 4
    if slow == "producer":
        assert ready == 0
    elif slow == "consumer":
        assert ready >= 3


@pytest.mark.parametrize("cache_mb,path", [(6, "device"), (2, "host")],
                         ids=["resident", "partial"])
def test_staging_rows_are_counted_once_a_step_on_the_path_taken(
        cache_mb, path, device_gather_on_cpu):
    """A cache that holds every row gives device rows (the step gathers
    them from the cache); a partial one host rows (``stack_batch_host``).
    Each step counts its rows once, in its one ``heta.stage.gather``."""
    sess = Heta(_tiny().updated(cache=dict(cache_mb=cache_mb)))
    sess.build_graph()
    sess.partition()
    sess.profile_and_cache()
    sess.compile()
    m = sess.fit(steps=3)
    recs = _mine(sess.obs_session)
    other = {"device": "host", "host": "device"}[path]
    per_step = []
    for s in range(3):
        gathers = [r for r in recs if r.step == s and r.name == "heta.stage.gather"]
        assert len(gathers) == 1
        counts = gathers[0].counts
        assert f"heta.stage.{other}_rows" not in counts
        per_step.append(counts[f"heta.stage.{path}_rows"])
    assert len(set(per_step)) == 1 and per_step[0] > 0
    assert m["counters"][f"heta.stage.{path}_rows"] == sum(per_step)
    assert ("heta.stage.snapshot" in m["spans"]) == (path == "host")


def test_learnable_steps_split_wait_pull_and_adam():
    sess = Heta(_tiny().updated(model=dict(hidden=32, train_learnable=True)))
    sess.build_graph()
    sess.partition()
    sess.profile_and_cache()
    sess.compile()
    sess.fit(steps=2)
    recs = _mine(sess.obs_session)
    for s in range(2):
        dev = next(r for r in recs if r.step == s and r.name == obs.DEVICE)
        kids = [r for r in recs if r.parent is dev]
        names = [r.name for r in sorted(kids, key=lambda r: r.t0)]
        assert names[0] == "heta.device.wait"
        assert set(names[1:]) == {"heta.update.pull", "heta.update.adam"}
        adam = [r for r in kids if r.name == "heta.update.adam"]
        assert all(any(c.parent is a and c.name == "heta.cache.fetch"
                       for c in recs) for a in adam)


def test_spans_are_on_the_profilers_host_plane(tmp_path):
    """Under the profiler, every ``heta.*`` span of a step is an event on
    ``/host:CPU``, nested as the records are, lasting what the recorder
    read to within a millisecond."""
    sess = Heta(_tiny())
    sess.build_graph()
    sess.partition()
    sess.profile_and_cache()
    sess.compile()
    sess.fit(steps=1)  # compiles outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        sess.fit(steps=2)
    finally:
        jax.profiler.stop_trace()
    recs = [r for r in _mine(sess.obs_session) if r.step in (1, 2)]
    assert {r.name for r in recs} >= {
        "heta.step", "heta.sample", "heta.stage", "heta.stage.snapshot",
        "heta.stage.gather", "heta.stage.put", "heta.device",
        "heta.device.wait"}
    path = sorted(glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True))[-1]
    data = jax.profiler.ProfileData.from_file(path)
    host = next(p for p in data.planes if p.name == "/host:CPU")
    events = sorted((e.start_ns, e.duration_ns, e.name) for line in host.lines
                    for e in line.events if e.name.startswith("heta."))
    # the same spans in the same order of start on both clocks
    mine = sorted(recs, key=lambda r: (r.t0, -r.seconds))
    assert [n for _, _, n in events] == [r.name for r in mine]
    for (start, dur, _), r in zip(events, mine):
        assert abs(dur * 1e-9 - r.seconds) < 1e-3
    at = {id(r): (s, s + d) for (s, d, _), r in zip(events, mine)}
    for r in mine:
        if r.parent is not None and id(r.parent) in at:
            (ps, pe), (s, e) = at[id(r.parent)], at[id(r)]
            assert ps <= s and e <= pe
    steps = [e for line in host.lines for e in line.events
             if e.name == "heta.step"]
    assert sorted(dict(e.stats)["step_num"] for e in steps) == [1, 2]


def test_a_window_is_none_without_its_steps():
    sid = obs.new_session()
    for i in range(3):
        with obs.span("heta.step", step=i, session=sid):
            with obs.span("heta.sample"):
                pass
    got = obs.window(1, 2, sid)
    assert sorted((r.step, r.name) for r in got) == [
        (1, "heta.sample"), (1, "heta.step"), (2, "heta.sample"),
        (2, "heta.step")]
    assert obs.window(1, 3, sid) is None
    assert obs.window(0, 1, obs.new_session()) is None
    assert np.isclose(sum(r.seconds for r in got if r.name == "heta.step"),
                      sum(r.seconds for r in obs.records()
                          if r.session == sid and r.step in (1, 2)
                          and r.name == "heta.step"))
