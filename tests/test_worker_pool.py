"""WorkerPool: stripe ordering, determinism vs the serial sampler,
exception propagation from worker processes, and lifecycle (close joins,
idempotence, no stray processes or shm segments)."""

import dataclasses
import os
import sys
import time

import numpy as np
import pytest

from repro.core.metatree import build_metatree
from repro.data import SampleStream
from repro.data.staging import arena_fields
from repro.data.worker_pool import (
    EpochSchedule,
    SampleStageTask,
    WorkerPool,
)
from repro.graph.sampler import NeighborSampler, SampleSpec
from repro.graph.shm import create_arena, live_segments, share_graph
from repro.graph.synthetic import ogbn_mag_like

pytestmark = pytest.mark.skipif(
    not os.path.isdir("/dev/shm"), reason="worker pool tests rely on /dev/shm"
)


# task classes live at module level so spawn can unpickle them in workers


@dataclasses.dataclass
class SquareTask:
    def setup(self):
        pass

    def __call__(self, i):
        return i * i

    def teardown(self):
        pass


@dataclasses.dataclass
class FailAtTask:
    fail_at: int

    def setup(self):
        pass

    def __call__(self, i):
        if i == self.fail_at:
            raise ZeroDivisionError(f"boom at {i}")
        return i

    def teardown(self):
        pass


@dataclasses.dataclass
class BadSetupTask:
    def setup(self):
        raise OSError("no graph for you")

    def __call__(self, i):  # pragma: no cover — setup always fails
        return i

    def teardown(self):
        pass


@dataclasses.dataclass
class JaxProbeTask:
    """Runs a real sampling task, then reports whether the worker process
    has imported jax."""

    inner: SampleStageTask

    def setup(self):
        self.inner.setup()

    def __call__(self, i):
        self.inner(i)
        return "jax" in sys.modules

    def teardown(self):
        self.inner.teardown()


@pytest.mark.parametrize("num_workers", [1, 2, 3])
def test_strict_order_and_finite_stop(num_workers):
    with WorkerPool(SquareTask(), num_workers=num_workers, depth=2,
                    num_items=7) as pool:
        assert list(pool) == [i * i for i in range(7)]
        with pytest.raises(StopIteration):
            next(pool)


def test_worker_exception_propagates_and_pool_closes():
    pool = WorkerPool(FailAtTask(fail_at=3), num_workers=2, depth=1,
                      num_items=10)
    got = []
    with pytest.raises(ZeroDivisionError, match="boom at 3"):
        for x in pool:
            got.append(x)
    assert got == [0, 1, 2]  # everything before the failure, in order
    assert all(not p.is_alive() for p in pool._procs)
    with pytest.raises(RuntimeError, match="closed"):
        next(pool)


def test_setup_failure_propagates():
    pool = WorkerPool(BadSetupTask(), num_workers=2, num_items=4)
    with pytest.raises(OSError, match="no graph for you"):
        list(pool)
    assert all(not p.is_alive() for p in pool._procs)


def test_close_joins_and_is_idempotent():
    pool = WorkerPool(SquareTask(), num_workers=2, depth=1)  # infinite stripe
    assert next(pool) == 0
    pool.close()
    assert all(not p.is_alive() for p in pool._procs)
    pool.close()  # idempotent
    with pytest.raises(RuntimeError, match="closed"):
        next(pool)


def test_validation():
    with pytest.raises(ValueError, match="num_workers"):
        WorkerPool(SquareTask(), num_workers=0)
    with pytest.raises(ValueError, match="depth"):
        WorkerPool(SquareTask(), num_workers=1, depth=0)


# --------------------------------------------------------------------------
# SampleStageTask — the HGNN sampling task over the shm store
# --------------------------------------------------------------------------


def _mag():
    g = ogbn_mag_like(scale=0.002)
    tree = build_metatree(g.metagraph(), g.target_type, 2)
    return g, SampleSpec.from_metatree(tree, [3, 2])


def _assert_batches_equal(a, b):
    np.testing.assert_array_equal(a.seeds, b.seeds)
    np.testing.assert_array_equal(a.labels, b.labels)
    for la, lb in zip(a.levels, b.levels):
        np.testing.assert_array_equal(la.nids, lb.nids)
        np.testing.assert_array_equal(la.mask, lb.mask)


def test_epoch_schedule_matches_session_formula():
    sched = EpochSchedule(epoch_seed_base=42, steps_per_epoch=5, start_step=3)
    # global step 3+9=12 -> epoch 2, index 2, seed base + 2*5
    assert sched.seed_and_index(9) == (42 + 10, 2)
    assert sched.seed_and_index(0) == (42, 3)


def _store_and_arena(g, spec, num_workers, depth, recipe=None, tables=None):
    """The shared graph store and a batch arena sized for its batches."""
    store = share_graph(g, include_features=False)
    probe = NeighborSampler(g, spec, 8, seed=5).batch_at(0, epoch_seed=0)
    arena = create_arena(arena_fields(probe, recipe=recipe, tables=tables),
                         num_workers=num_workers, depth=depth, tables=tables)
    return store, arena


def _stream(pool, arena, spec, n):
    """The pool's first ``n`` items as ``(batch, host_arrays, host_s)``,
    resolved from their arena slots."""
    return SampleStream(num_steps=n, pool=pool, arena=arena, spec=spec,
                        finish_stage=lambda batch, host: host)


@pytest.mark.parametrize("num_workers", [1, 3])
def test_pool_batches_bit_identical_to_serial(num_workers):
    g, spec = _mag()
    serial = NeighborSampler(g, spec, 8, seed=5)
    E = serial.steps_per_epoch()
    store, arena = _store_and_arena(g, spec, num_workers, 2)
    try:
        task = SampleStageTask(
            handle=store.handle, spec=spec, batch_size=8, sampler_seed=5,
            schedule=EpochSchedule(77, E), arena=arena.handle,
        )
        n = min(E + 2, 6)  # cross an epoch boundary when the graph allows
        with WorkerPool(task, num_workers=num_workers, depth=2,
                        num_items=n) as pool, \
                _stream(pool, arena, spec, n) as stream:
            for i, (batch, host, host_s) in enumerate(stream):
                seed, idx = EpochSchedule(77, E).seed_and_index(i)
                _assert_batches_equal(batch, serial.batch_at(idx, epoch_seed=seed))
                assert host is None and host_s >= 0.0
            assert i == n - 1
    finally:
        store.unlink()
        arena.unlink()
    assert not live_segments(store.handle.segment)
    assert not live_segments(arena.handle.segment)


def test_sampler_workers_never_import_jax():
    """Workers are spawned while the consumer holds the accelerator, which
    belongs to one process at a time: a worker that imported jax would
    claim or wait for the chip.  Sampling and staging must stay jax-free."""
    g, spec = _mag()
    # two slots per worker: each of the 4 items has a slot of its own, so
    # no write waits for a release the probe never makes
    store, arena = _store_and_arena(g, spec, 2, 2)
    try:
        task = SampleStageTask(
            handle=store.handle, spec=spec, batch_size=8, sampler_seed=5,
            schedule=EpochSchedule(77, NeighborSampler(
                g, spec, 8, seed=5).steps_per_epoch()),
            arena=arena.handle,
        )
        with WorkerPool(JaxProbeTask(task), num_workers=2, depth=1,
                        num_items=4) as pool:
            assert list(pool) == [False] * 4
    finally:
        store.unlink()
        arena.unlink()


def test_worker_staging_matches_consumer_staging():
    """The recipe path: a worker-staged frozen-table batch must be
    bit-identical to staging the same batch on the consumer (both run
    repro.data.staging.stack_batch_host)."""
    jnp = pytest.importorskip("jax.numpy")
    from repro.core.hgnn import HGNNConfig
    from repro.core.meta_partition import meta_partition
    from repro.core.raf import assign_branches
    from repro.core import raf_spmd
    from repro.data.staging import stack_batch_host

    g, _ = _mag()
    mp_ = meta_partition(g, 2, num_layers=2)
    spec = SampleSpec.from_metatree(mp_.metatree, [3, 2])
    assignment = assign_branches(spec, mp_)
    cfg = HGNNConfig(model="rgcn", hidden=32, num_layers=2, num_heads=4,
                     num_classes=g.num_classes, learnable_dim=16)
    feat_dims = {t: g.feat_dim(t) for t in g.num_nodes if g.feat_dim(t)}
    plan = raf_spmd.build_plan(spec, assignment, cfg, feat_dims)
    recipe = raf_spmd.stack_recipe(plan)

    rng = np.random.default_rng(0)
    tables = {
        t: (g.features[t].astype(np.float32) if t in g.features
            else rng.standard_normal((g.num_nodes[t], 16)).astype(np.float32))
        for t in g.num_nodes
    }
    serial = NeighborSampler(g, spec, 8, seed=5)
    store, arena = _store_and_arena(g, spec, 2, 2, recipe=recipe,
                                    tables=tables)
    try:
        task = SampleStageTask(
            handle=store.handle, spec=spec, batch_size=8, sampler_seed=5,
            schedule=EpochSchedule(9, serial.steps_per_epoch()), recipe=recipe,
            arena=arena.handle,
        )
        with WorkerPool(task, num_workers=2, depth=2, num_items=3) as pool, \
                _stream(pool, arena, spec, 3) as stream:
            for i, (batch, host, _) in enumerate(stream):
                assert host is not None
                ref = stack_batch_host(
                    recipe, serial.batch_at(i, epoch_seed=9), tables)
                assert set(host) == set(ref)
                for k in ref:
                    np.testing.assert_array_equal(host[k], ref[k])
                # and the full executor path gives the same device arrays
                dev = raf_spmd.stack_batch(plan, batch, tables)
                for k in ref:
                    np.testing.assert_array_equal(np.asarray(dev[k]), ref[k])
            assert i == 2
    finally:
        store.unlink()
        arena.unlink()


def test_pool_shutdown_leaves_no_processes_quickly():
    g, spec = _mag()
    store, arena = _store_and_arena(g, spec, 2, 1)
    try:
        task = SampleStageTask(
            handle=store.handle, spec=spec, batch_size=8, sampler_seed=0,
            schedule=EpochSchedule(0, NeighborSampler(g, spec, 8).steps_per_epoch()),
            arena=arena.handle,
        )
        pool = WorkerPool(task, num_workers=2, depth=1)  # infinite
        next(pool)
        t0 = time.perf_counter()
        pool.close()  # a worker blocked on a full sub-ring stops too
        assert time.perf_counter() - t0 < 10.0
        assert all(not p.is_alive() for p in pool._procs)
    finally:
        store.unlink()
        arena.unlink()
