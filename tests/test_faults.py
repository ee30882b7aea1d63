"""Fault injection + worker supervision (DESIGN.md §12): FaultPlan
semantics, dead-worker respawn with deterministic stripe replay, arena
slot invalidation, writer stall detection, and the end-to-end chaos drill
— a pooled frozen-snapshot fit that loses a sampler worker mid-run must
finish with bit-identical losses."""

import dataclasses
import os
import time

import numpy as np
import pytest

from repro.core.metatree import build_metatree
from repro.data.faults import (
    KILL_EXIT_CODE,
    FaultPlan,
    FaultSpec,
    InjectedFault,
)
from repro.data.worker_pool import (
    EpochSchedule,
    SampleStageTask,
    WorkerDiedError,
    WorkerPool,
)
from repro.graph.sampler import NeighborSampler, SampleSpec
from repro.graph.shm import create_arena, live_segments, share_graph
from repro.graph.synthetic import ogbn_mag_like

pytestmark = pytest.mark.skipif(
    not os.path.isdir("/dev/shm"), reason="fault drills rely on /dev/shm"
)


# --------------------------------------------------------------------------
# FaultPlan — deterministic coordinates, no wall-clock
# --------------------------------------------------------------------------


def test_fault_spec_validation():
    with pytest.raises(ValueError, match="kind"):
        FaultSpec("segfault", step=0)
    with pytest.raises(ValueError, match="step"):
        FaultSpec("kill_worker", step=-1)
    with pytest.raises(ValueError, match="count"):
        FaultSpec("fail_flush", step=0, count=0)
    with pytest.raises(ValueError, match="delay_s"):
        FaultSpec("delay_flush", step=0, delay_s=-0.1)


def test_fault_plan_worker_queries():
    plan = FaultPlan((
        FaultSpec("kill_worker", step=5, worker=1),
        FaultSpec("raise_item", step=2),
        FaultSpec("poison_slot", step=4, first_attempt_only=False),
    ))
    assert plan
    # worker filter: only worker 1, only item 5
    assert plan.kill_at(1, 0, 5)
    assert not plan.kill_at(0, 0, 5)
    assert not plan.kill_at(1, 0, 3)
    # first_attempt_only (default): the respawned incarnation sails through
    assert not plan.kill_at(1, 1, 5)
    assert plan.raise_at(0, 0, 2) and not plan.raise_at(0, 1, 2)
    # first_attempt_only=False keeps firing on replays
    assert plan.poison_at(0, 3, 4)
    assert not FaultPlan()


def test_fault_plan_flush_queries():
    plan = FaultPlan((
        FaultSpec("fail_flush", step=3, count=2),
        FaultSpec("delay_flush", step=0, delay_s=0.25),
    ))
    assert plan.flush_fault(2) is None
    assert plan.flush_fault(3) is not None and plan.flush_fault(4) is not None
    assert plan.flush_fault(5) is None
    assert plan.flush_delay(0) == 0.25
    assert plan.flush_delay(1) == 0.0


def test_fault_plan_json_round_trip():
    plan = FaultPlan((
        FaultSpec("kill_worker", step=5, worker=1),
        FaultSpec("fail_flush", step=0, count=3, first_attempt_only=False),
        FaultSpec("delay_flush", step=2, delay_s=0.5),
    ))
    assert FaultPlan.from_json(plan.to_json()) == plan
    assert FaultPlan.from_json(FaultPlan().to_json()) == FaultPlan()


# --------------------------------------------------------------------------
# worker supervision — respawn budget, stripe replay, loud failure modes
# --------------------------------------------------------------------------

# task classes live at module level so spawn can unpickle them in workers


@dataclasses.dataclass
class ChaosTask:
    """Minimal pool task with the SampleStageTask fault hooks."""

    faults: FaultPlan

    def setup(self):
        pass

    def bind_worker(self, wid, attempt):
        self._wid, self._attempt = wid, attempt

    def __call__(self, i):
        if self.faults.kill_at(self._wid, self._attempt, i):
            os._exit(KILL_EXIT_CODE)  # silent death: no queue message
        if self.faults.raise_at(self._wid, self._attempt, i):
            raise InjectedFault(f"scheduled raise at {i}")
        return i * i

    def teardown(self):
        pass


def test_respawn_replays_stripe_and_records_event():
    """Killing worker 1 mid-stripe: the supervisor respawns it from the
    consumer's position and the full ordered stream still arrives."""
    task = ChaosTask(FaultPlan((FaultSpec("kill_worker", step=5, worker=1),)))
    with WorkerPool(task, num_workers=2, depth=2, num_items=12,
                    max_restarts=2, restart_backoff_s=0.01) as pool:
        assert list(pool) == [i * i for i in range(12)]
        assert len(pool.restarts) == 1
        ev = pool.restarts[0]
        assert ev["wid"] == 1
        assert ev["exitcode"] == KILL_EXIT_CODE
        assert ev["attempt"] == 1
        # detection may fire before the kill item: os._exit can lose
        # already-queued items still in the feeder thread, and replay
        # covers them -- so the position is any of worker 1's stripe
        # items up to the kill point
        assert ev["item"] in (1, 3, 5)
        assert ev["downtime_s"] >= 0.0


def test_restart_budget_exhausted_raises_with_exit_code():
    task = ChaosTask(FaultPlan((FaultSpec("kill_worker", step=3),)))
    pool = WorkerPool(task, num_workers=2, depth=1, num_items=8,
                      max_restarts=0)
    got = []
    with pytest.raises(WorkerDiedError, match=r"code 73.*restarts used: 0/0"):
        for x in pool:
            got.append(x)
    assert got == [i * i for i in range(len(got))]  # prefix stayed ordered
    assert all(not p.is_alive() for p in pool._procs)
    with pytest.raises(RuntimeError, match="closed"):
        next(pool)


def test_persistent_kill_exhausts_respawn_budget():
    """first_attempt_only=False kills every incarnation at the same item:
    the budget burns down and the final error names it."""
    task = ChaosTask(FaultPlan((
        FaultSpec("kill_worker", step=2, first_attempt_only=False),)))
    pool = WorkerPool(task, num_workers=2, depth=1, num_items=8,
                      max_restarts=1, restart_backoff_s=0.01)
    with pytest.raises(WorkerDiedError, match=r"restarts used: 1/1"):
        list(pool)
    assert len(pool.restarts) == 1  # one respawn happened before giving up


def test_injected_raise_propagates_without_respawn():
    """raise_item is a *loud* failure (the worker ships the traceback);
    supervision only covers silent deaths, so no restart is consumed."""
    task = ChaosTask(FaultPlan((FaultSpec("raise_item", step=2),)))
    pool = WorkerPool(task, num_workers=2, depth=1, num_items=8,
                      max_restarts=2)
    with pytest.raises(InjectedFault, match="scheduled raise at 2"):
        list(pool)
    assert pool.restarts == []
    assert all(not p.is_alive() for p in pool._procs)


def test_on_worker_death_hook_runs_before_respawn():
    deaths = []
    task = ChaosTask(FaultPlan((FaultSpec("kill_worker", step=0, worker=0),)))
    with WorkerPool(task, num_workers=2, depth=1, num_items=6,
                    max_restarts=1, restart_backoff_s=0.01,
                    on_worker_death=deaths.append) as pool:
        assert list(pool) == [i * i for i in range(6)]
    assert deaths == [0]


def test_supervision_validation():
    with pytest.raises(ValueError, match="max_restarts"):
        WorkerPool(ChaosTask(FaultPlan()), num_workers=1, max_restarts=-1)


# --------------------------------------------------------------------------
# SampleStageTask under faults — replay determinism over the shm store
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


def test_sampler_kill_replay_bit_identical_to_serial():
    """A killed sampler worker's stripe is replayed by its replacement:
    every delivered batch still matches the serial sampler bit-for-bit."""
    from repro.data import SampleStream
    from repro.data.staging import arena_fields

    g, spec = _mag()
    serial = NeighborSampler(g, spec, 8, seed=5)
    E = serial.steps_per_epoch()
    store = share_graph(g, include_features=False)
    arena = create_arena(arena_fields(serial.batch_at(0, epoch_seed=0)),
                         num_workers=2, depth=2)
    try:
        task = SampleStageTask(
            handle=store.handle, spec=spec, batch_size=8, sampler_seed=5,
            schedule=EpochSchedule(77, E), arena=arena.handle,
            faults=FaultPlan((FaultSpec("kill_worker", step=3),)),
        )
        n = 6
        # as the session wires it: a death poisons the dead worker's
        # sub-ring before its replacement replays the stripe
        with WorkerPool(task, num_workers=2, depth=2, num_items=n,
                        max_restarts=1, restart_backoff_s=0.01,
                        on_worker_death=arena.invalidate_worker_slots) as pool, \
                SampleStream(num_steps=n, pool=pool, arena=arena, spec=spec,
                             finish_stage=lambda b, host: host) as stream:
            for i, (batch, host, host_s) in enumerate(stream):
                seed, idx = EpochSchedule(77, E).seed_and_index(i)
                _assert_batches_equal(batch, serial.batch_at(idx, epoch_seed=seed))
                assert host is None and host_s >= 0.0
            assert i == n - 1
            assert len(pool.restarts) == 1
            assert pool.restarts[0]["exitcode"] == KILL_EXIT_CODE
    finally:
        store.unlink()
        arena.unlink()
    assert not live_segments(store.handle.segment)
    assert not live_segments(arena.handle.segment)


def _probe_fields():
    return {"x": np.zeros((4, 3), np.float32), "y": np.zeros(4, np.int64)}


def test_poisoned_slot_resolves_loudly_and_heals_on_rewrite():
    """poison_slot models a torn write: resolve raises instead of returning
    garbage, and the next begin_write heals the stamp."""
    with create_arena(_probe_fields(), num_workers=1, depth=1) as a:
        a.begin_write(0, 0)
        a.slot_views(0, writable=True)["x"][:] = 7.0
        a.end_write(0, 0)
        a.poison_slot(0)
        with pytest.raises(RuntimeError, match="invalidated"):
            a.resolve(0, 0)
        # release still works (backpressure bookkeeping is separate) and
        # the replacement generation heals the stamp
        a.release(0, 0)
        assert a.wait_writable(0, 1, timeout=1.0)
        a.begin_write(0, 1)
        a.slot_views(0, writable=True)["x"][:] = 8.0
        a.end_write(0, 1)
        assert float(a.resolve(0, 1)["x"][0, 0]) == 8.0


def test_invalidate_worker_slots_scopes_to_one_worker():
    """The supervisor's death hook poisons only the dead worker's sub-ring;
    the surviving worker's in-flight slots stay resolvable."""
    with create_arena(_probe_fields(), num_workers=2, depth=2) as a:
        for i in range(4):  # one generation of every slot
            slot, use = a.handle.slot_for(i)
            a.begin_write(slot, use)
            a.slot_views(slot, writable=True)["x"][:] = float(i)
            a.end_write(slot, use)
        a.invalidate_worker_slots(0)
        for i in (0, 2):  # worker 0's items
            slot, use = a.handle.slot_for(i)
            with pytest.raises(RuntimeError, match="invalidated"):
                a.resolve(slot, use)
        for i in (1, 3):  # worker 1 untouched
            slot, use = a.handle.slot_for(i)
            assert float(a.resolve(slot, use)["x"][0, 0]) == float(i)


def test_arena_writer_stall_raises_named_error():
    """A wedged consumer (never releases) must fail the writer loudly
    after write_timeout_s, not hang it forever."""
    from repro.data.staging import arena_fields

    g, spec = _mag()
    serial = NeighborSampler(g, spec, 8, seed=0)
    store = share_graph(g, include_features=False)
    arena = create_arena(arena_fields(serial.batch_at(0, epoch_seed=0)),
                         num_workers=1, depth=1)
    task = SampleStageTask(
        handle=store.handle, spec=spec, batch_size=8, sampler_seed=0,
        schedule=EpochSchedule(0, serial.steps_per_epoch()),
        arena=arena.handle, write_timeout_s=0.1,
    )
    try:
        task.bind_worker(0, 0)
        task.setup()
        ref = task(0)
        assert ref.slot == 0 and ref.use == 0
        from repro.graph.shm import ArenaStalledError

        t0 = time.perf_counter()
        with pytest.raises(ArenaStalledError, match="not writable"):
            task(1)  # same slot, generation 1 -- never released
        assert time.perf_counter() - t0 < 5.0
    finally:
        task.teardown()
        arena.unlink()
        store.unlink()


# --------------------------------------------------------------------------
# end-to-end chaos drill: pooled fit loses a worker, losses bit-identical
# --------------------------------------------------------------------------


def _chaos_config():
    from repro.api import (CacheConfig, DataConfig, FaultConfig, HetaConfig,
                           ModelConfig, PartitionConfig, PipelineConfig,
                           RunConfig)

    return HetaConfig(
        data=DataConfig(dataset="ogbn-mag", scale=0.002, fanouts=(3, 2),
                        batch_size=8),
        partition=PartitionConfig(num_partitions=2),
        model=ModelConfig(hidden=32),
        cache=CacheConfig(cache_mb=2, presample_epochs=1),
        run=RunConfig(executor="raf_spmd", steps=10, lr=1e-2, seed=0),
        pipeline=PipelineConfig(num_workers=2, depth=2),
        faults=FaultConfig(max_worker_restarts=2, worker_backoff_s=0.01),
    )


def test_pooled_fit_survives_worker_kill_bit_identical():
    """ISSUE 9 acceptance (a): a pooled frozen-snapshot fit that loses a
    worker mid-run respawns it, replays the stripe, and produces
    bit-identical losses to the undisturbed run."""
    from repro.api import Heta

    ref = Heta(_chaos_config()).run()

    drill = Heta(_chaos_config())
    drill.fault_plan = FaultPlan((FaultSpec("kill_worker", step=5),))
    try:
        got = drill.run()
        pool = drill._pool_cache[2]
        assert len(pool.restarts) == 1
        ev = pool.restarts[0]
        assert ev["exitcode"] == KILL_EXIT_CODE and ev["attempt"] == 1
    finally:
        drill.close_pipeline()
    assert got["losses"] == ref["losses"]  # bit-identical


def test_pooled_fit_budget_exhaustion_is_loud():
    """With respawn disabled the same drill dies with the named error —
    never a hang, never silent truncation of the epoch."""
    from repro.api import Heta

    drill = Heta(_chaos_config().updated(faults=dict(max_worker_restarts=0)))
    drill.fault_plan = FaultPlan((FaultSpec("kill_worker", step=5),))
    try:
        with pytest.raises(WorkerDiedError, match="code 73"):
            drill.run()
    finally:
        drill.close_pipeline()
