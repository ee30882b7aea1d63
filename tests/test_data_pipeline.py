"""Host data pipelines: Prefetcher lifecycle, token pipeline determinism /
sharding / liveness, per-batch sampler RNG, and the async SampleStream."""

import threading
import time

import numpy as np
import pytest

from repro.data import Prefetcher, SampleStream, SyntheticCorpus, TokenPipeline


def test_corpus_deterministic_and_shifted():
    c = SyntheticCorpus(vocab=1000, seq_len=32, num_shards=4, seed=7)
    a = c.sequence(1, 5)
    b = c.sequence(1, 5)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(c.sequence(1, 6), a)
    batch = c.batch(0, 0, 4)
    np.testing.assert_array_equal(batch["tokens"][:, 1:], batch["labels"][:, :-1])
    assert batch["tokens"].max() < 1000


def test_pipeline_shapes_and_progress():
    c = SyntheticCorpus(vocab=512, seq_len=16, num_shards=4)
    pipe = TokenPipeline(c, global_batch=8, prefetch=2)
    try:
        b1 = next(pipe)
        b2 = next(pipe)
        assert b1["tokens"].shape == (8, 16)
        assert b1["labels"].shape == (8, 16)
        assert not np.array_equal(b1["tokens"], b2["tokens"])
    finally:
        pipe.close()


def test_pipeline_multi_host_split():
    c = SyntheticCorpus(vocab=512, seq_len=16, num_shards=4)
    p0 = TokenPipeline(c, global_batch=8, host_id=0, num_hosts=2)
    p1 = TokenPipeline(c, global_batch=8, host_id=1, num_hosts=2)
    try:
        b0, b1 = next(p0), next(p1)
        assert b0["tokens"].shape == (4, 16)  # half the global batch each
        assert not np.array_equal(b0["tokens"], b1["tokens"])  # disjoint shards
    finally:
        p0.close()
        p1.close()


def test_pipeline_feeds_training():
    import jax

    import repro.configs.all_archs  # noqa: F401
    from repro.configs.base import ARCHS
    from repro.models import init_train_state, make_train_step

    cfg = ARCHS["qwen2-1.5b"].reduced()
    c = SyntheticCorpus(vocab=cfg.vocab, seq_len=32, num_shards=2)
    pipe = TokenPipeline(c, global_batch=2)
    try:
        state = init_train_state(cfg, jax.random.PRNGKey(0))
        step = make_train_step(cfg, donate=False)
        import jax.numpy as jnp

        for _ in range(2):
            b = next(pipe)
            state, loss = step(state, {k: jnp.asarray(v) for k, v in b.items()})
            assert np.isfinite(float(loss))
    finally:
        pipe.close()


# --------------------------------------------------------------------------
# Prefetcher — the shared producer (lifecycle contract)
# --------------------------------------------------------------------------


def test_prefetcher_order_and_finite_stop():
    with Prefetcher(lambda i: i * i, depth=2, num_items=5) as pf:
        assert list(pf) == [0, 1, 4, 9, 16]
        with pytest.raises(StopIteration):  # exhausted stays exhausted
            next(pf)


def test_prefetcher_close_joins_and_next_raises():
    pf = Prefetcher(lambda i: i, depth=2)
    assert next(pf) == 0
    pf.close()
    assert not pf._thread.is_alive()  # producer actually joined
    with pytest.raises(RuntimeError, match="closed"):
        next(pf)
    pf.close()  # idempotent


def test_prefetcher_producer_exception_propagates():
    def make(i):
        if i == 2:
            raise ZeroDivisionError("boom at 2")
        return i

    pf = Prefetcher(make, depth=1)
    assert next(pf) == 0
    assert next(pf) == 1
    with pytest.raises(ZeroDivisionError, match="boom at 2"):
        # drain until the failure surfaces (depth may buffer good items)
        for _ in range(10):
            next(pf)
    assert not pf._thread.is_alive()  # failure also shuts the producer down
    with pytest.raises(RuntimeError, match="closed"):
        next(pf)


def test_prefetcher_close_unblocks_full_queue_producer():
    """close() must join even while the producer is blocked on a full queue."""
    pf = Prefetcher(lambda i: i, depth=1)
    time.sleep(0.1)  # let the producer fill the queue and block on put
    pf.close()
    assert not pf._thread.is_alive()


def test_prefetcher_close_idempotent_after_producer_failure():
    """A failure already shut the stream down from __next__; every later
    close() — explicit, context exit, or GC — must be a silent no-op."""
    def make(i):
        raise ValueError("dead on arrival")

    pf = Prefetcher(make, depth=1)
    with pytest.raises(ValueError, match="dead on arrival"):
        next(pf)
    import warnings

    with warnings.catch_warnings():
        warnings.simplefilter("error")
        pf.close()
        pf.close(warn=False)
        pf.__del__()  # the GC path must never raise or warn
    assert not pf._thread.is_alive()


def test_prefetcher_del_mid_run_is_quiet():
    """GC'ing a live stream (no explicit close) joins the thread without
    warning noise — the interpreter-shutdown contract, exercised live."""
    import gc
    import warnings

    pf = Prefetcher(lambda i: i, depth=1)
    assert next(pf) == 0
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        del pf
        gc.collect()


def test_prefetcher_runs_in_background_thread():
    tids = []

    def make(i):
        tids.append(threading.get_ident())
        return i

    with Prefetcher(make, depth=1, num_items=2) as pf:
        list(pf)
    assert tids and all(t != threading.get_ident() for t in tids)


def test_token_pipeline_close_then_next_raises():
    c = SyntheticCorpus(vocab=64, seq_len=8, num_shards=2)
    pipe = TokenPipeline(c, global_batch=4)
    next(pipe)
    pipe.close()
    with pytest.raises(RuntimeError, match="closed"):
        next(pipe)


# --------------------------------------------------------------------------
# per-batch sampler RNG (the async-pipeline determinism contract)
# --------------------------------------------------------------------------


def _mag_sampler(seed=0, batch=8):
    from repro.core.metatree import build_metatree
    from repro.graph.sampler import NeighborSampler, SampleSpec
    from repro.graph.synthetic import ogbn_mag_like

    g = ogbn_mag_like(scale=0.002)
    tree = build_metatree(g.metagraph(), g.target_type, 2)
    spec = SampleSpec.from_metatree(tree, [3, 2])
    return NeighborSampler(g, spec, batch, seed=seed)


def _assert_batches_equal(a, b):
    np.testing.assert_array_equal(a.seeds, b.seeds)
    for la, lb in zip(a.levels, b.levels):
        np.testing.assert_array_equal(la.nids, lb.nids)
        np.testing.assert_array_equal(la.mask, lb.mask)


def test_batch_at_pure_function_of_position():
    """Same (seed, epoch, step) -> bit-identical batch, across restarts and
    out-of-order access."""
    s1, s2 = _mag_sampler(seed=5), _mag_sampler(seed=5)
    b_fwd = [s1.batch_at(i, epoch_seed=11) for i in range(3)]
    b_rev = [s2.batch_at(i, epoch_seed=11) for i in (2, 1, 0)][::-1]
    for x, y in zip(b_fwd, b_rev):
        _assert_batches_equal(x, y)
    # distinct positions / epochs actually differ
    assert not np.array_equal(b_fwd[0].levels[0].nids, b_fwd[1].levels[0].nids)
    assert not np.array_equal(
        b_fwd[0].levels[0].nids,
        _mag_sampler(seed=5).batch_at(0, epoch_seed=12).levels[0].nids,
    )


def test_epoch_iterator_matches_batch_at():
    s = _mag_sampler(seed=1)
    for i, b in zip(range(3), s.epoch(shuffle=True, seed=4)):
        _assert_batches_equal(b, s.batch_at(i, epoch_seed=4))


def test_adhoc_sample_batch_replays_across_instances():
    s1, s2 = _mag_sampler(seed=9), _mag_sampler(seed=9)
    seeds = s1.graph.train_nodes[:8]
    for _ in range(3):  # same call sequence -> same batches
        _assert_batches_equal(s1.sample_batch(seeds), s2.sample_batch(seeds))


# --------------------------------------------------------------------------
# SampleStream — background sample+stage
# --------------------------------------------------------------------------


def test_sample_stream_matches_serial():
    s = _mag_sampler(seed=2)
    staged = lambda b: int(b.seeds.sum())
    with SampleStream(lambda i: s.batch_at(i, epoch_seed=7), staged,
                      num_steps=4, depth=2) as stream:
        got = list(stream)
    assert len(got) == 4
    s2 = _mag_sampler(seed=2)
    for i, (batch, arrays, host_s) in enumerate(got):
        ref = s2.batch_at(i, epoch_seed=7)
        _assert_batches_equal(batch, ref)
        assert arrays == int(ref.seeds.sum())
        assert host_s >= 0.0


def test_sample_stream_defer_stage_runs_on_consumer():
    s = _mag_sampler(seed=2)
    stage_tids = []

    def staged(b):
        stage_tids.append(threading.get_ident())
        return 0

    with SampleStream(lambda i: s.batch_at(i, epoch_seed=7), staged,
                      num_steps=2, depth=2, defer_stage=True) as stream:
        list(stream)
    # "fresh" policy: staging happened on the consumer thread
    assert stage_tids and all(t == threading.get_ident() for t in stage_tids)


def test_sample_stream_shutdown_on_exception():
    def bad_stage(b):
        raise RuntimeError("stage failed")

    s = _mag_sampler(seed=2)
    stream = SampleStream(lambda i: s.batch_at(i, epoch_seed=7), bad_stage,
                          num_steps=4, depth=2)
    with pytest.raises(RuntimeError, match="stage failed"):
        list(stream)
    assert not stream._prefetcher._thread.is_alive()  # clean shutdown


# --------------------------------------------------------------------------
# pipeline parity across HGNN models (the relation-module IR runs every
# model on every executor — hgt × raf_spmd being the per-node-type case)
# --------------------------------------------------------------------------


@pytest.mark.parametrize("model,executor", [
    ("rgat", "raf_spmd"),
    ("hgt", "raf_spmd"),
    ("hgt", "raf"),
])
def test_pipeline_parity_models(model, executor):
    """With frozen feature tables staging is time-invariant, so pipeline
    on/off must be bit-identical for every (model, executor) pair."""
    from repro.api import (DataConfig, Heta, HetaConfig, ModelConfig,
                           PartitionConfig, RunConfig)

    def cfg(pipelined):
        c = HetaConfig(
            data=DataConfig(dataset="ogbn-mag", scale=0.002, fanouts=(3, 2),
                            batch_size=16),
            partition=PartitionConfig(num_partitions=2),
            model=ModelConfig(model=model, hidden=32, train_learnable=False),
            run=RunConfig(executor=executor, steps=3, lr=1e-2, seed=0),
        )
        return c.updated(pipeline=dict(enabled=True)) if pipelined else c

    off = Heta(cfg(False)).run()
    on = Heta(cfg(True)).run()
    assert off["losses"] == on["losses"]  # bit-identical
    assert on["pipeline"] and not off["pipeline"]
    assert np.all(np.isfinite(on["losses"]))


def test_sample_stream_facade_validates_modes():
    s = _mag_sampler(seed=2)
    with pytest.raises(ValueError, match="worker_task"):
        SampleStream(lambda i: s.batch_at(i, epoch_seed=1), lambda b: b,
                     num_workers=2)
    with pytest.raises(ValueError, match="make_batch"):
        SampleStream(stage=lambda b: b, num_workers=0)
    with pytest.raises(ValueError, match="num_workers"):
        SampleStream(lambda i: i, lambda b: b, num_workers=-1)


# --------------------------------------------------------------------------
# multi-worker host pipeline (process pool over the shm graph store):
# workers ∈ {0, 1, 4} must be bit-identical to the serial loop on every
# executor — frozen tables make staging time-invariant, and batch_at purity
# makes the stripe decomposition invisible (DESIGN.md §9)
# --------------------------------------------------------------------------


# On the CPU backend raf_spmd stages on the host, in the workers where the
# executor lets them.  With the resident gather let engage there
# (``device_gather_on_cpu``), the default 4 MiB cache holds the whole tiny
# graph, so raf_spmd stages node ids on the consumer and the workers only
# sample.


def _staged_in_workers(sess) -> bool:
    """Whether the pool's workers staged the batches: the executor gave
    them a staging recipe and the consumer gathered no row itself."""
    return (sess.executor.worker_stage_recipe(sess, sess.plan) is not None
            and "heta.stage.gather" not in sess.results()["spans"])


def _only_sampled_in_workers(sess) -> bool:
    """Whether the pool's workers only sampled: no staging recipe, and the
    consumer staged node ids for the device gather."""
    c = sess.results()["counters"]
    return (sess.executor.worker_stage_recipe(sess, sess.plan) is None
            and c.get("heta.stage.device_rows", 0) > 0
            and "heta.stage.host_rows" not in c)


@pytest.mark.parametrize("executor", ["vanilla", "raf", "raf_spmd"])
def test_worker_pool_parity_all_executors(executor):
    from repro.api import (DataConfig, Heta, HetaConfig, ModelConfig,
                           PartitionConfig, RunConfig)
    from repro.graph.shm import live_segments

    def run(workers):
        c = HetaConfig(
            data=DataConfig(dataset="ogbn-mag", scale=0.002, fanouts=(3, 2),
                            batch_size=16),
            partition=PartitionConfig(num_partitions=2),
            model=ModelConfig(hidden=32, train_learnable=False),
            run=RunConfig(executor=executor, steps=3, lr=1e-2, seed=0),
        )
        if workers is not None:
            c = c.updated(pipeline=dict(enabled=True, num_workers=workers))
        sess = Heta(c)
        try:
            r = sess.run()
            if workers and executor == "raf_spmd":
                assert _staged_in_workers(sess), workers
            return r
        finally:
            sess.close_pipeline()

    serial = run(None)
    for w in (0, 1, 4):
        r = run(w)
        assert serial["losses"] == r["losses"], (executor, w)
        assert r["sampler_workers"] == w
        assert r["samples_per_s"] > 0
        if w:  # arena mode: queue carries SlotRef descriptors, not arrays
            assert 0 < r["queue_bytes_per_step"] < 1024, (executor, w)
    assert serial["sampler_workers"] == 0
    assert not live_segments()  # every run released its store


def test_worker_pool_legacy_pickle_path_still_bit_identical():
    """``pipeline.arena=False`` keeps the PR-5 pickle transport: same
    batches, same losses, megabyte queue items (the cost the arena
    removes)."""
    from repro.graph.shm import live_segments

    (on, on_staged), (off, off_staged) = (_pickle_or_arena_run(True),
                                          _pickle_or_arena_run(False))
    assert on_staged and off_staged
    assert on["losses"] == off["losses"]
    assert on["queue_bytes_per_step"] < 1024 < off["queue_bytes_per_step"]
    assert not live_segments()


def _pickle_or_arena_run(arena, check=_staged_in_workers):
    from repro.api import (DataConfig, Heta, HetaConfig, ModelConfig,
                           PartitionConfig, RunConfig)

    c = HetaConfig(
        data=DataConfig(dataset="ogbn-mag", scale=0.002, fanouts=(3, 2),
                        batch_size=16),
        partition=PartitionConfig(num_partitions=2),
        model=ModelConfig(hidden=32, train_learnable=False),
        run=RunConfig(executor="raf_spmd", steps=3, lr=1e-2, seed=0),
    ).updated(pipeline=dict(enabled=True, num_workers=2, arena=arena))
    sess = Heta(c)
    try:
        return sess.run(), check(sess)
    finally:
        sess.close_pipeline()


def test_worker_pool_resident_cache_pickle_and_arena_bit_identical(
        device_gather_on_cpu):
    """With a cache that holds every row the workers only sample, by either
    transport, and both train as the serial loop does."""
    from repro.api import (DataConfig, Heta, HetaConfig, ModelConfig,
                           PartitionConfig, RunConfig)

    serial = Heta(HetaConfig(
        data=DataConfig(dataset="ogbn-mag", scale=0.002, fanouts=(3, 2),
                        batch_size=16),
        partition=PartitionConfig(num_partitions=2),
        model=ModelConfig(hidden=32, train_learnable=False),
        run=RunConfig(executor="raf_spmd", steps=3, lr=1e-2, seed=0),
    )).run()
    for arena in (True, False):
        r, sampled_only = _pickle_or_arena_run(arena,
                                               check=_only_sampled_in_workers)
        assert sampled_only, arena
        assert r["losses"] == serial["losses"], arena


def _learnable_run(workers, snapshot="stale", arena=True):
    """A learnable raf_spmd fit; its result gains ``staged_in_workers`` and
    ``only_sampled_in_workers``."""
    from repro.api import (DataConfig, Heta, HetaConfig, ModelConfig,
                           PartitionConfig, RunConfig)

    c = HetaConfig(
        data=DataConfig(dataset="ogbn-mag", scale=0.002, fanouts=(3, 2),
                        batch_size=16),
        partition=PartitionConfig(num_partitions=2),
        model=ModelConfig(hidden=32, train_learnable=True),
        run=RunConfig(executor="raf_spmd", steps=3, lr=1e-2, seed=0),
    )
    if workers is not None:
        c = c.updated(pipeline=dict(enabled=True, num_workers=workers,
                                    snapshot=snapshot, arena=arena))
    sess = Heta(c)
    try:
        r = sess.run()
        r["staged_in_workers"] = _staged_in_workers(sess)
        r["only_sampled_in_workers"] = _only_sampled_in_workers(sess)
        return r
    finally:
        sess.close_pipeline()


def test_worker_pool_learnable_fresh_is_bit_exact():
    """Under the "fresh" snapshot policy pool workers only sample — staging
    runs consumer-side against the just-updated tables, so pooled losses
    are bit-exact at every worker count."""
    serial = _learnable_run(None)
    for w in (0, 1, 4):
        assert serial["losses"] == _learnable_run(w, snapshot="fresh")["losses"], w


def test_worker_pool_learnable_stale_stages_in_workers_bounded():
    """Under the default "stale" policy with the batch arena, workers stage
    against seqlock-republished table snapshots at most the ring depth
    behind the trainer (DESIGN.md §11): the loss trajectory tracks the
    serial path within optimization noise, and the queue stays zero-pickle
    (SlotRef descriptors only)."""
    serial = _learnable_run(None)
    stale = _learnable_run(2, snapshot="stale")
    assert stale["staged_in_workers"]
    assert np.allclose(serial["losses"], stale["losses"], atol=5e-2)
    assert 0 < stale["queue_bytes_per_step"] < 1024  # descriptors, not arrays


def test_worker_pool_learnable_stale_resident_cache_is_bit_exact(
        device_gather_on_cpu):
    """With a cache that holds every row, staging reads no table (node ids;
    the step gathers the rows it updates), so under the "stale" policy the
    workers only sample and pooled losses are bit-exact."""
    serial = _learnable_run(None)
    stale = _learnable_run(2, snapshot="stale")
    assert stale["only_sampled_in_workers"]
    assert serial["losses"] == stale["losses"]
    assert 0 < stale["queue_bytes_per_step"] < 1024  # descriptors, not arrays


def test_pool_persists_across_fits_and_stays_bit_identical():
    """Consecutive fit() calls reuse one pool + shm store (spawn amortized)
    and the two-fit pooled trajectory equals one serial fit of the same
    length; a serial step() in between forces a clean respawn."""
    from repro.graph.shm import live_segments

    sess, serial = _two_fits_pooled()
    # the workers staged every pooled batch; the consumer only the serial one
    assert sess.executor.worker_stage_recipe(sess, sess.plan) is not None
    assert sess.results()["spans"]["heta.stage.gather"]["count"] == 1
    assert sess.losses == serial["losses"]
    sess.close_pipeline()
    assert sess._pool_cache is None
    sess.close_pipeline()  # idempotent
    assert not live_segments()


def _two_fits_pooled():
    """Two pooled fits, a serial step, and a pooled fit again (the pool
    reused, then respawned) on a session, and the serial run of as many
    steps; the session is returned open."""
    from repro.api import (DataConfig, Heta, HetaConfig, ModelConfig,
                           PartitionConfig, RunConfig)

    def cfg(workers=None):
        c = HetaConfig(
            data=DataConfig(dataset="ogbn-mag", scale=0.002, fanouts=(3, 2),
                            batch_size=16),
            partition=PartitionConfig(num_partitions=2),
            model=ModelConfig(hidden=32, train_learnable=False),
                run=RunConfig(executor="raf_spmd", steps=6, lr=1e-2, seed=0),
        )
        if workers is not None:
            c = c.updated(pipeline=dict(enabled=True, num_workers=workers))
        return c

    serial = Heta(cfg()).run()

    sess = Heta(cfg(workers=2))
    sess.build_graph(); sess.partition(); sess.profile_and_cache(); sess.compile()
    sess.fit(2)
    pool_a = sess._pool_cache[2]
    sess.fit(2)
    assert sess._pool_cache[2] is pool_a  # reused, not respawned
    sess.step()  # serial step desyncs the stripe position...
    sess.fit(1)
    assert sess._pool_cache[2] is not pool_a  # ...so the pool respawned
    return sess, serial


def test_pool_persists_across_fits_on_the_resident_path(device_gather_on_cpu):
    """The persistent pool on a cache that holds every row: the workers
    only sample, and the pooled trajectory still equals the serial one."""
    sess, serial = _two_fits_pooled()
    try:
        assert _only_sampled_in_workers(sess)
        assert sess.losses == serial["losses"]
    finally:
        sess.close_pipeline()


def test_evaluate_with_workers_matches_serial():
    from repro.api import (DataConfig, Heta, HetaConfig, ModelConfig,
                           PartitionConfig, RunConfig)

    def build(workers):
        c = HetaConfig(
            data=DataConfig(dataset="ogbn-mag", scale=0.002, fanouts=(3, 2),
                            batch_size=16),
            partition=PartitionConfig(num_partitions=2),
            model=ModelConfig(hidden=32, train_learnable=False),
            run=RunConfig(executor="raf_spmd", steps=0, lr=1e-2, seed=0),
        )
        if workers is not None:
            c = c.updated(pipeline=dict(enabled=True, num_workers=workers))
        sess = Heta(c)
        sess.run()
        return sess

    ref = build(None).evaluate(num_batches=2)
    pooled = build(2).evaluate(num_batches=2)
    assert ref["loss"] == pooled["loss"]
    assert ref["num_batches"] == pooled["num_batches"] == 2


def test_seedless_epochs_vary_but_replay_deterministically():
    """epoch() without a seed draws fresh samples each call (multi-epoch
    training loops keep sampling variance), yet a fresh sampler replays the
    same sequence of epochs."""
    s1, s2 = _mag_sampler(seed=3), _mag_sampler(seed=3)
    e1a, e1b = next(s1.epoch()), next(s1.epoch())
    assert not np.array_equal(e1a.levels[0].nids, e1b.levels[0].nids)
    _assert_batches_equal(e1a, next(s2.epoch()))
    _assert_batches_equal(e1b, next(s2.epoch()))
