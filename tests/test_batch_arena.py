"""Batch arena (DESIGN.md §11): slot ring protocol, seqlock'd staging
tables, pack/unpack round trips, zero-pickle descriptors through a real
worker pool, and crash/lifecycle hygiene."""

import dataclasses
import os
import pickle
import threading
import time

import numpy as np
import pytest

from repro.core.metatree import build_metatree
from repro.data.staging import (
    BATCH_PREFIX,
    HOST_PREFIX,
    arena_fields,
    pack_batch_arrays,
    pack_batch_into,
    unpack_slot,
)
from repro.data.worker_pool import (
    EpochSchedule,
    SampleStageTask,
    SlotRef,
    WorkerDiedError,
    WorkerPool,
)
from repro.graph.sampler import NeighborSampler, SampleSpec
from repro.graph.shm import (
    SEGMENT_PREFIX,
    attach_arena,
    create_arena,
    live_segments,
    share_arrays,
    share_graph,
)
from repro.graph.synthetic import ogbn_mag_like

pytestmark = pytest.mark.skipif(
    not os.path.isdir("/dev/shm"), reason="arena tests rely on /dev/shm"
)


def _mag():
    g = ogbn_mag_like(scale=0.002)
    tree = build_metatree(g.metagraph(), g.target_type, 2)
    return g, SampleSpec.from_metatree(tree, [3, 2])


def _probe_fields():
    return {"x": np.zeros((4, 3), np.float32), "y": np.zeros(4, np.int64)}


def _own_segments():
    """The shared-memory segments this test process created and still
    holds (segment names carry their creator's pid): the leak check, blind
    to the segments of tests running beside it in other processes."""
    return live_segments(f"{SEGMENT_PREFIX}{os.getpid():x}-")


def _assert_batches_equal(a, b):
    np.testing.assert_array_equal(a.seeds, b.seeds)
    np.testing.assert_array_equal(a.labels, b.labels)
    for la, lb in zip(a.levels, b.levels):
        np.testing.assert_array_equal(la.nids, lb.nids)
        np.testing.assert_array_equal(la.mask, lb.mask)


# --------------------------------------------------------------------------
# slot ring protocol
# --------------------------------------------------------------------------


def test_slot_for_is_per_worker_sub_ring():
    with create_arena(_probe_fields(), num_workers=2, depth=2) as a:
        h = a.handle
        assert h.n_slots == 4
        # stripe item i -> worker i % 2; each worker cycles its own 2 slots
        assert [h.slot_for(i) for i in range(8)] == [
            (0, 0), (2, 0), (1, 0), (3, 0), (0, 1), (2, 1), (1, 1), (3, 1)]


def test_wraparound_reuse_and_stale_generation_rejected():
    """A slot is reused across generations; resolving the wrong generation
    (a descriptor outliving its slot) raises instead of returning torn
    data."""
    with create_arena(_probe_fields(), num_workers=1, depth=1) as a:
        for use in range(3):
            assert a.wait_writable(0, use, timeout=1.0)
            a.begin_write(0, use)
            a.slot_views(0, writable=True)["x"][:] = float(use)
            a.end_write(0, use)
            views = a.resolve(0, use)
            assert float(views["x"][0, 0]) == float(use)
            a.release(0, use)
        with pytest.raises(RuntimeError, match="generation"):
            a.resolve(0, 0)  # stale descriptor after two overwrites
        a.begin_write(0, 3)
        with pytest.raises(RuntimeError, match="write_seq"):
            a.resolve(0, 3)  # mid-write (odd seq) is a protocol violation


def test_backpressure_blocks_until_release():
    """With every generation of a slot in flight the writer's gate stays
    shut (timeout) and opens as soon as the consumer releases."""
    with create_arena(_probe_fields(), num_workers=1, depth=1) as a:
        a.begin_write(0, 0)
        a.end_write(0, 0)
        # generation 1 must wait: generation 0 not yet consumed
        t0 = time.perf_counter()
        assert not a.wait_writable(0, 1, timeout=0.05)
        assert time.perf_counter() - t0 >= 0.05

        stop = threading.Event()
        assert not a.wait_writable(0, 1, stop=stop, timeout=0.05)

        def _release():
            time.sleep(0.02)
            a.release(0, 0)

        t = threading.Thread(target=_release)
        t.start()
        assert a.wait_writable(0, 1, timeout=2.0)
        t.join()


def test_stop_event_exits_backpressure_wait():
    with create_arena(_probe_fields(), num_workers=1, depth=1) as a:
        a.begin_write(0, 0)
        a.end_write(0, 0)
        stop = threading.Event()

        def _trip():
            time.sleep(0.02)
            stop.set()

        t = threading.Thread(target=_trip)
        t.start()
        t0 = time.perf_counter()
        assert not a.wait_writable(0, 1, stop=stop, timeout=5.0)
        assert time.perf_counter() - t0 < 4.0  # exited on stop, not timeout
        t.join()


# --------------------------------------------------------------------------
# seqlock'd staging tables
# --------------------------------------------------------------------------


def test_immutable_tables_are_zero_copy_views():
    tab = np.arange(12, dtype=np.float32).reshape(3, 4)
    with create_arena(_probe_fields(), num_workers=1, depth=1,
                      tables={"paper": tab}) as a:
        views, ver = a.read_tables()
        assert ver == 0
        np.testing.assert_array_equal(views["paper"], tab)
        assert not views["paper"].flags.writeable  # view, not copy
        with pytest.raises(RuntimeError, match="immutable"):
            a.publish_tables({"paper": tab})


def test_publish_bumps_version_and_readers_see_whole_updates():
    tab = np.zeros((64, 16), np.float32)
    with create_arena(_probe_fields(), num_workers=1, depth=1,
                      tables={"t": tab}, tables_mutable=True) as a:
        a.publish_tables({"t": np.full_like(tab, 7.0)})
        out, ver = a.read_tables()
        assert ver == 2 and np.all(out["t"] == 7.0)
        assert out["t"].flags.writeable  # mutable path returns a copy


def test_seqlock_retries_torn_reads_under_concurrent_writer():
    """A writer thread republishes uniform-valued tables as fast as it can;
    every read must observe one publish in full — a mixed-value table is a
    torn read the seqlock failed to retry."""
    tab = np.zeros((256, 32), np.float32)
    with create_arena(_probe_fields(), num_workers=1, depth=1,
                      tables={"t": tab}, tables_mutable=True) as a:
        stop = threading.Event()

        def _writer():
            v = 0.0
            while not stop.is_set():
                v += 1.0
                a.publish_tables({"t": np.full_like(tab, v)})

        w = threading.Thread(target=_writer)
        w.start()
        try:
            deadline = time.monotonic() + 1.0
            reads = 0
            while time.monotonic() < deadline:
                out, ver = a.read_tables()
                assert ver % 2 == 0  # never returns mid-publish
                vals = np.unique(out["t"])
                assert len(vals) == 1, f"torn read: {vals}"
                reads += 1
            assert reads > 0
        finally:
            stop.set()
            w.join()


# --------------------------------------------------------------------------
# pack / unpack round trip (staging helpers)
# --------------------------------------------------------------------------


def test_pack_unpack_round_trip_is_bit_identical():
    g, spec = _mag()
    s = NeighborSampler(g, spec, 8, seed=5)
    batch = s.batch_at(0, epoch_seed=3)
    fields = arena_fields(batch)
    assert all(k.startswith(BATCH_PREFIX) for k in fields)
    with create_arena(fields, num_workers=1, depth=1) as a:
        a.begin_write(0, 0)
        pack_batch_into(a.slot_views(0, writable=True), batch)
        a.end_write(0, 0)
        got, host = unpack_slot(a.resolve(0, 0), spec)
        assert host is None
        _assert_batches_equal(got, batch)
        # flat reference: same arrays as the pure-dict pack
        flat = pack_batch_arrays(batch)
        views = a.resolve(0, 0)
        for k in flat:
            np.testing.assert_array_equal(views[k], flat[k])


def test_arena_fields_includes_host_arrays_with_recipe():
    pytest.importorskip("jax")
    from repro.core.hgnn import HGNNConfig
    from repro.core.meta_partition import meta_partition
    from repro.core.raf import assign_branches
    from repro.core import raf_spmd
    from repro.data.staging import stack_batch_host

    g, _ = _mag()
    mp_ = meta_partition(g, 2, num_layers=2)
    spec = SampleSpec.from_metatree(mp_.metatree, [3, 2])
    cfg = HGNNConfig(model="rgcn", hidden=32, num_layers=2, num_heads=4,
                     num_classes=g.num_classes, learnable_dim=16)
    feat_dims = {t: g.feat_dim(t) for t in g.num_nodes if g.feat_dim(t)}
    plan = raf_spmd.build_plan(spec, assign_branches(spec, mp_), cfg,
                               feat_dims)
    recipe = raf_spmd.stack_recipe(plan)
    rng = np.random.default_rng(0)
    tables = {
        t: (g.features[t].astype(np.float32) if t in g.features
            else rng.standard_normal((g.num_nodes[t], 16)).astype(np.float32))
        for t in g.num_nodes
    }
    s = NeighborSampler(g, spec, 8, seed=5)
    batch = s.batch_at(0, epoch_seed=3)
    fields = arena_fields(batch, recipe=recipe, tables=tables)
    assert any(k.startswith(HOST_PREFIX) for k in fields)
    with create_arena(fields, num_workers=1, depth=1) as a:
        views = a.slot_views(0, writable=True)
        pack_batch_into(views, batch)
        stack_batch_host(recipe, batch, tables, out=views,
                         prefix=HOST_PREFIX)
        got, host = unpack_slot(a.slot_views(0), spec)
        _assert_batches_equal(got, batch)
        ref = stack_batch_host(recipe, batch, tables)
        assert set(host) == set(ref)
        for k in ref:
            np.testing.assert_array_equal(host[k], ref[k])


# --------------------------------------------------------------------------
# through a real worker pool
# --------------------------------------------------------------------------


def test_pool_arena_descriptors_stay_tiny_and_batches_match_serial():
    """The zero-pickle guarantee: with the arena the queue carries SlotRef
    descriptors under 1 KiB, and the resolved batches are bit-identical to
    the serial sampler."""
    g, spec = _mag()
    serial = NeighborSampler(g, spec, 8, seed=5)
    E = serial.steps_per_epoch()
    store = share_graph(g, include_features=False)
    batch0 = serial.batch_at(0, epoch_seed=77)
    arena = create_arena(arena_fields(batch0), num_workers=2, depth=2)
    try:
        task = SampleStageTask(
            handle=store.handle, spec=spec, batch_size=8, sampler_seed=5,
            schedule=EpochSchedule(77, E), arena=arena.handle,
        )
        n = min(E + 2, 8)  # cross a wrap-around of each sub-ring
        with WorkerPool(task, num_workers=2, depth=2, num_items=n) as pool:
            for i, ref in enumerate(pool):
                assert isinstance(ref, SlotRef)
                assert len(pickle.dumps(ref)) < 1024
                assert (ref.slot, ref.use) == arena.handle.slot_for(i)
                batch, host = unpack_slot(arena.resolve(ref.slot, ref.use),
                                          spec)
                assert host is None
                seed, idx = EpochSchedule(77, E).seed_and_index(i)
                _assert_batches_equal(
                    batch, serial.batch_at(idx, epoch_seed=seed))
                arena.release(ref.slot, ref.use)
    finally:
        store.unlink()
        arena.unlink()
    assert not _own_segments()


@dataclasses.dataclass
class CrashAfterWriteTask:
    """Writes one slot, then dies hard mid-stripe — the leak test below
    checks the parent can still unlink every segment."""

    arena: object
    crash_at: int = 1

    def setup(self):
        self._a = attach_arena(self.arena)

    def bind_stop(self, stop):
        self._stop = stop

    def __call__(self, i):
        if i == self.crash_at:
            os._exit(13)  # hard crash: no teardown, no atexit
        slot, use = self._a.handle.slot_for(i)
        if not self._a.wait_writable(slot, use, stop=self._stop, timeout=30):
            return None
        self._a.begin_write(slot, use)
        self._a.slot_views(slot, writable=True)["x"][:] = float(i)
        self._a.end_write(slot, use)
        return SlotRef(step=i, slot=slot, use=use, host_s=0.0)

    def teardown(self):
        self._a.close()


def test_worker_crash_surfaces_and_leaks_no_segments():
    arena = create_arena(_probe_fields(), num_workers=1, depth=2)
    try:
        task = CrashAfterWriteTask(arena=arena.handle)
        pool = WorkerPool(task, num_workers=1, depth=2, num_items=4)
        got = []
        with pytest.raises(WorkerDiedError, match="exited"):
            for ref in pool:
                got.append(ref)
                arena.release(ref.slot, ref.use)
        # item 0 may or may not flush through the queue's feeder thread
        # before os._exit kills it; whatever arrives is in stripe order
        assert [r.step for r in got] in ([], [0])
        assert all(not p.is_alive() for p in pool._procs)
    finally:
        arena.unlink()
    assert not _own_segments()  # owner-side unlink survives worker death


def test_create_arena_validates_and_is_transactional():
    with pytest.raises(ValueError, match="num_workers"):
        create_arena(_probe_fields(), num_workers=0, depth=2)
    with pytest.raises(ValueError, match="num_workers"):
        create_arena(_probe_fields(), num_workers=1, depth=0)
    # a bad table must not leak the segment
    with pytest.raises(AttributeError):
        create_arena(_probe_fields(), num_workers=1, depth=1,
                     tables={"t": object()})
    assert not _own_segments()


def test_the_leak_check_sees_this_process_and_no_other():
    """``_own_segments`` catches a segment this process leaves behind, and
    passes over one named for another process (pid 0: no process's own
    check, nor the stale-segment janitor, ever takes it)."""
    other = share_arrays({"x": np.zeros(4)},
                         name=f"{SEGMENT_PREFIX}0-{os.getpid():x}")
    leaked = create_arena(_probe_fields(), num_workers=1, depth=1)
    try:
        assert _own_segments() == [leaked.handle.segment]
        assert other.handle.segment in live_segments()
    finally:
        leaked.unlink()
        other.unlink()
    assert not _own_segments()
