"""Paper Fig. 10 — per-stage training time breakdown.

Stages mirror the paper's: sampling, feature fetching (staging), forward+
backward (device step), learnable-feature/model update.  Vanilla adds
projected network time for remote features; Heta's stages are all local
(plus the Θ(B·hidden) partial exchange, part of the step).

Built entirely on the public session + staged-executor surface
(``Executor.stage`` / ``Executor.step_staged`` — no private imports, no
hand-rolled training loop), and reports the async-pipeline overlap: the
``pipelined`` mode re-runs the same steps through ``pipeline.enabled`` and
emits serial vs overlapped step time plus the overlap fraction
(host work hidden behind the device step).

``--num-workers 0,1,2,4`` adds the multi-worker sampling sweep
(DESIGN.md §9): pure sampling throughput of the thread producer vs N-process
pools over the shared-memory graph store, identical batches per position at
every worker count.  Rows are machine-readable (``samples_per_s``, ``cpus``,
``speedup_vs_1w``) and land in ``BENCH_pipeline.json`` via
``--records-out`` — the host-pipeline leg of the perf trajectory."""

from __future__ import annotations

import os
import time

from benchmarks._util import (dram_random_time, emit, net_time, timed_fit,
                              write_records)
from repro import obs
from repro.api import (
    CacheConfig, DataConfig, Heta, HetaConfig, ModelConfig, PartitionConfig,
    RunConfig,
)
from repro.core.comm import vanilla_comm_bytes, vanilla_update_bytes
from repro.core.meta_partition import random_edge_cut


def _session(scale, batch, fanouts, steps, train_learnable=True, **pipeline):
    cfg = HetaConfig(
        data=DataConfig(dataset="ogbn-mag", scale=scale, fanouts=fanouts,
                        batch_size=batch),
        partition=PartitionConfig(num_partitions=2),
        model=ModelConfig(hidden=64, train_learnable=train_learnable),
        cache=CacheConfig(cache_mb=2),
        run=RunConfig(executor="raf_spmd", steps=steps, seed=3),
    )
    if pipeline:
        cfg = cfg.updated(pipeline=pipeline)
    sess = Heta(cfg)
    sess.build_graph()
    sess.partition()
    sess.profile_and_cache()
    sess.compile()
    return sess


def _update_s(sess) -> float:
    """Seconds the session's spans spent in the sparse update so far: the
    feature-gradient read-back and host Adam, without the device wait."""
    spans = obs.totals(sess.obs_session)
    return sum(spans[n]["seconds"] for n in ("heta.update.pull", "heta.update.adam")
               if n in spans)


def run(scale: float = 0.002, batch: int = 32, fanouts=(5, 4), steps: int = 4,
        pipelined: bool = True):
    sess = _session(scale, batch, fanouts, steps)
    ex, plan = sess.executor, sess.plan

    stages = {"sample": 0.0, "fetch": 0.0, "step": 0.0, "update": 0.0}
    cut = random_edge_cut(sess.graph, 2)
    v_fetch = v_upd = 0.0
    it = sess.sampler.epoch(shuffle=True, seed=sess.config.run.seed)
    for i in range(steps):
        t0 = time.perf_counter()
        b = next(it)
        stages["sample"] += time.perf_counter() - t0

        t0 = time.perf_counter()
        arrays = ex.stage(sess, plan, b)
        stages["fetch"] += time.perf_counter() - t0

        upd0 = _update_s(sess)
        with obs.scope(i, sess.obs_session):
            sess.state, _, dt = ex.step_staged(sess, plan, sess.state, b, arrays)
        upd = _update_s(sess) - upd0
        stages["step"] += dt - upd
        stages["update"] += upd

        v_fetch += net_time(vanilla_comm_bytes(b, cut, sess.feat_dims,
                                               bytes_per_elem=2), 16)
        ub = vanilla_update_bytes(b, cut, sess.graph, bytes_per_elem=2)
        v_upd += net_time(ub, 8) + dram_random_time(ub)

    total = sum(stages.values())
    for k, v in stages.items():
        emit(f"breakdown/heta/{k}", v / steps * 1e6, f"{100*v/total:.0f}% of step")
    emit("breakdown/vanilla_extra/remote_fetch", v_fetch / steps * 1e6,
         "projected 100Gbps (Heta: 0)")
    emit("breakdown/vanilla_extra/remote_update", v_upd / steps * 1e6,
         "projected (Heta: local, cached)")

    if pipelined:
        overlap_stats = run_pipelined(scale, batch, fanouts, steps)
        stages["pipelined"] = overlap_stats
    return stages


def run_pipelined(scale: float = 0.002, batch: int = 32, fanouts=(5, 4),
                  steps: int = 8):
    """Serial vs async-pipeline wall time over identical batches.

    Both runs train the same model on the same data (per-batch sampler
    RNG); the pipelined one prefetches sample+stage in the background, so
    its per-step wall time drops toward the device step time and the
    hidden-host-work share is reported as the overlap fraction.  Learnable
    features are frozen here so step shapes stay fixed — with them on, the
    per-batch unique-row counts force sparse-update recompiles whose
    process-warm jit cache would bias whichever mode runs second (the
    per-stage loop in :func:`run` still measures the learnable path)."""
    results = {}
    for mode, pipeline in (("serial", {}), ("overlapped", dict(enabled=True))):
        sess = _session(scale, batch, fanouts, steps, train_learnable=False,
                        **pipeline)
        wall_per_step, overlap = timed_fit(sess, steps)
        results[mode] = dict(wall_per_step_s=wall_per_step,
                             overlap_fraction=overlap)
        emit(f"breakdown/pipeline/{mode}_step", wall_per_step * 1e6,
             f"overlap fraction {overlap:.2f}")
    emit("breakdown/pipeline/overlap_fraction",
         results["overlapped"]["overlap_fraction"],
         "share of host sample+stage hidden behind the device step")
    return results


def run_worker_sweep(scale: float = 0.01, batch: int = 64, fanouts=(10, 10),
                     steps: int = 32, workers=(0, 1, 2, 4),
                     repeats: int = 3, arena: bool = True,
                     legacy_diagnosis: bool = True):
    """Sampling-throughput scaling of the host pipeline's producer.

    Every configuration materializes the *same* batches for the same
    positions (``batch_at`` purity); only who computes them differs —
    the single thread (``workers=0``) or an N-process pool over the
    shared-memory graph store.  Throughput is measured at the consumer,
    after a warmup that absorbs spawn + first-touch cost, as the best of
    ``repeats`` consecutive ``steps``-batch segments (best-of de-noises
    interference from co-tenants on shared machines), so the number is
    the steady-state rate the device loop would see.  ``cpus`` is recorded
    with every row: scaling saturates at the core count, so a 2-core
    container cannot show more than 2x of *aggregate* CPU — though it can
    exceed 2x vs a 1-worker baseline that leaves the consumer core idle.

    ``arena=True`` (default) routes pool batches through the shm batch
    arena (DESIGN.md §11): the queue carries ~10^2-byte SlotRef
    descriptors instead of ~10^6-byte pickled batches.  The pickle
    transport was exactly the ``workers1`` regression of the PR-5 rows
    (50k vs 99.5k samples/s): one worker pays serialize + pipe-write and
    the consumer pays read + deserialize of the full batch it could have
    sampled itself — pure overhead the arena removes.
    ``legacy_diagnosis=True`` re-times ``workers=1`` over the pickle path
    and emits the ``queue_bytes_per_item`` of both transports so the
    regression (and its fix) stays visible in ``BENCH_pipeline.json``."""
    import pickle

    from repro.data.prefetch import Prefetcher
    from repro.data.staging import arena_fields, unpack_slot
    from repro.data.worker_pool import (EpochSchedule, SampleStageTask,
                                        WorkerPool)
    from repro.graph.sampler import NeighborSampler
    from repro.graph.shm import create_arena, share_graph

    sess = Heta(HetaConfig(
        data=DataConfig(dataset="ogbn-mag", scale=scale, fanouts=fanouts,
                        batch_size=batch),
        partition=PartitionConfig(num_partitions=2),
        run=RunConfig(seed=3),
    ))
    sess.build_graph()
    sess.partition()
    g, spec = sess.graph, sess.spec
    E = NeighborSampler(g, spec, batch).steps_per_epoch()
    sched = EpochSchedule(7, E)
    warm = 2

    def time_config(w, use_arena):
        """(samples/s, mean queue item bytes) for one producer config."""
        n = steps * repeats + warm
        store = ring = None
        qbytes = []
        if w == 0:
            sampler = NeighborSampler(g, spec, batch, seed=1)

            def make(i, _s=sampler, _sched=sched):
                seed, idx = _sched.seed_and_index(i)
                return _s.batch_at(idx, epoch_seed=seed)

            src = Prefetcher(make, depth=2, num_items=n, name="sweep-thread")
        else:
            store = share_graph(g, include_features=False)
            if use_arena:
                probe = NeighborSampler(g, spec, batch,
                                        seed=1).batch_at(0, epoch_seed=7)
                ring = create_arena(arena_fields(probe), num_workers=w,
                                    depth=2)
            task = SampleStageTask(
                handle=store.handle, spec=spec, batch_size=batch,
                sampler_seed=1, schedule=sched,
                arena=ring.handle if ring is not None else None)
            src = WorkerPool(task, num_workers=w, depth=2, num_items=n)
        try:
            it = iter(src)

            def draw():
                item = next(it)
                if ring is not None:
                    if not qbytes:
                        qbytes.append(len(pickle.dumps(item)))
                    unpack_slot(ring.resolve(item.slot, item.use), spec)
                    ring.release(item.slot, item.use)
                elif w > 0 and not qbytes:
                    qbytes.append(len(pickle.dumps(item)))

            for _ in range(warm):
                draw()
            wall = float("inf")
            for _ in range(repeats):
                t0 = time.perf_counter()
                for _ in range(steps):
                    draw()
                wall = min(wall, time.perf_counter() - t0)
        finally:
            src.close()
            if store is not None:
                store.unlink()
            if ring is not None:
                ring.unlink()
        return steps * batch / wall, (qbytes[0] if qbytes else 0)

    results = {}
    for w in workers:
        sps, qb = time_config(w, use_arena=arena)
        results[w] = sps
        emit(f"pipeline/sampling/workers{w}", batch / sps * 1e6,
             f"{sps:,.0f} samples/s"
             + (f", {qb} B/queue item" if w else ""),
             workers=w, samples_per_s=round(sps, 1), batch_size=batch,
             fanouts=list(fanouts), kind="sampling",
             queue_bytes_per_item=qb, arena=bool(arena and w),
             cpus=os.cpu_count())
    if legacy_diagnosis and arena and 1 in results:
        sps, qb = time_config(1, use_arena=False)
        emit("pipeline/sampling/workers1_legacy", batch / sps * 1e6,
             f"{sps:,.0f} samples/s over the pickle queue "
             f"({qb} B/item — the PR-5 workers1 regression)",
             workers=1, samples_per_s=round(sps, 1), batch_size=batch,
             fanouts=list(fanouts), kind="sampling",
             queue_bytes_per_item=qb, arena=False, cpus=os.cpu_count())
        emit("pipeline/sampling/workers1_arena_vs_legacy", 0.0,
             f"{results[1] / sps:.2f}x from descriptor-only queues",
             workers=1, speedup_vs_legacy=round(results[1] / sps, 3),
             kind="sampling_scaling", cpus=os.cpu_count())
    base = results.get(1)
    if base:
        for w in sorted(results):
            if w > 1:
                emit(f"pipeline/sampling/scaling_1_to_{w}",
                     0.0, f"{results[w] / base:.2f}x vs 1 worker "
                     f"({os.cpu_count()} cpus)",
                     workers=w, speedup_vs_1w=round(results[w] / base, 3),
                     kind="sampling_scaling", cpus=os.cpu_count())
    if 0 in results and len(results) > 1:
        best = max(v for k, v in results.items() if k > 0)
        emit("pipeline/sampling/pool_vs_thread", 0.0,
             f"best pool {best / results[0]:.2f}x the single thread",
             speedup_vs_thread=round(best / results[0], 3),
             kind="sampling_scaling", cpus=os.cpu_count())
    return results


def run_consumer_completion(scale: float = 0.01, batch: int = 64,
                            fanouts=(10, 10), repeats: int = 30):
    """Consumer-side completion cost of a worker-staged batch.

    With frozen tables the sampler workers assemble the full stacked host
    arrays (``stack_batch_host``); all the consumer does is finish staging.
    ``stage_from_host`` feeds those host views straight into the sharded
    ``device_put`` — the device-put-free path — while the reference row
    re-times the copying completion it replaced (``np.array`` per field,
    then the same device put).  Both produce bit-identical device arrays;
    the delta is pure consumer-thread overhead that the overlap window
    cannot hide.  ``cpus`` is stamped on every row as usual."""
    import numpy as np

    from repro.data.staging import stack_batch_host

    sess = _session(scale, batch, fanouts, steps=1, train_learnable=False)
    ex, plan = sess.executor, sess.plan
    recipe = ex.worker_stage_recipe(sess, plan)
    if recipe is None:  # pragma: no cover - frozen tables always have one
        raise SystemExit("no worker stage recipe; cannot probe completion")
    tables = sess.engine.tables_snapshot()
    b = sess._batch_for_step(0)
    host = stack_batch_host(recipe, b, tables)

    import jax

    def t_of(fn):
        fn()  # warmup (compile + first-touch)
        ts = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            ts.append(time.perf_counter() - t0)
        return sorted(ts)[len(ts) // 2]

    t_free = t_of(lambda: ex.stage_from_host(sess, plan, b, host))
    t_copy = t_of(lambda: ex.stage_from_host(
        sess, plan, b, {k: np.array(v) for k, v in host.items()}))
    nbytes = int(sum(v.nbytes for v in host.values()))
    emit("pipeline/consumer/stage_from_host", t_free * 1e6,
         f"device-put-free completion of {nbytes / 1e6:.1f} MB staged host "
         "arrays", kind="consumer_completion", copy_free=True,
         staged_bytes=nbytes, batch_size=batch, fanouts=list(fanouts))
    emit("pipeline/consumer/copying_reference", t_copy * 1e6,
         "same completion behind a per-field np.array copy",
         kind="consumer_completion", copy_free=False, staged_bytes=nbytes,
         batch_size=batch, fanouts=list(fanouts))
    emit("pipeline/consumer/copy_free_speedup", 0.0,
         f"{t_copy / t_free:.2f}x from skipping the host copy",
         kind="consumer_completion",
         speedup_vs_copy=round(t_copy / t_free, 3))
    return {"stage_from_host_s": t_free, "copying_s": t_copy}


def run_pinning_probe(scale: float = 0.01, batch: int = 64, fanouts=(10, 10),
                      steps: int = 32, workers: int = 2, repeats: int = 3):
    """``pipeline.pin_workers`` on/off over the arena pool, same batches.

    Pinning helps when the scheduler migrates sampler workers across cores
    mid-epoch (cold caches); on a container with fewer cores than workers
    it is expected to be a wash or a small loss — the rows record whichever
    way it goes, stamped with ``cpus`` so readers can tell the two regimes
    apart.  No timing gate anywhere."""
    from repro.data.prefetch import Prefetcher  # noqa: F401  (parity import)
    from repro.data.staging import arena_fields, unpack_slot
    from repro.data.worker_pool import (EpochSchedule, SampleStageTask,
                                        WorkerPool)
    from repro.graph.sampler import NeighborSampler
    from repro.graph.shm import create_arena, share_graph

    sess = Heta(HetaConfig(
        data=DataConfig(dataset="ogbn-mag", scale=scale, fanouts=fanouts,
                        batch_size=batch),
        partition=PartitionConfig(num_partitions=2),
        run=RunConfig(seed=3),
    ))
    sess.build_graph()
    sess.partition()
    g, spec = sess.graph, sess.spec
    E = NeighborSampler(g, spec, batch).steps_per_epoch()
    sched = EpochSchedule(7, E)
    warm = 2

    def time_pool(pin: bool) -> float:
        n = steps * repeats + warm
        store = share_graph(g, include_features=False)
        probe = NeighborSampler(g, spec, batch, seed=1).batch_at(0,
                                                                 epoch_seed=7)
        ring = create_arena(arena_fields(probe), num_workers=workers, depth=2)
        task = SampleStageTask(handle=store.handle, spec=spec,
                               batch_size=batch, sampler_seed=1,
                               schedule=sched, arena=ring.handle,
                               pin_cpus=pin)
        src = WorkerPool(task, num_workers=workers, depth=2, num_items=n)
        try:
            it = iter(src)

            def draw():
                item = next(it)
                unpack_slot(ring.resolve(item.slot, item.use), spec)
                ring.release(item.slot, item.use)

            for _ in range(warm):
                draw()
            wall = float("inf")
            for _ in range(repeats):
                t0 = time.perf_counter()
                for _ in range(steps):
                    draw()
                wall = min(wall, time.perf_counter() - t0)
        finally:
            src.close()
            store.unlink()
            ring.unlink()
        return steps * batch / wall

    base = time_pool(False)
    pinned = time_pool(True)
    for name, sps, pin in (("unpinned", base, False), ("pinned", pinned, True)):
        emit(f"pipeline/sampling/workers{workers}_{name}", batch / sps * 1e6,
             f"{sps:,.0f} samples/s", workers=workers, pin_workers=pin,
             samples_per_s=round(sps, 1), batch_size=batch,
             fanouts=list(fanouts), kind="sampling_pinning")
    emit(f"pipeline/sampling/pinning_effect_w{workers}", 0.0,
         f"{pinned / base:.2f}x pinned vs unpinned ({os.cpu_count()} cpus)",
         workers=workers, speedup_pinned=round(pinned / base, 3),
         kind="sampling_pinning")
    return {"unpinned": base, "pinned": pinned}


def _parse_workers(s: str):
    return tuple(int(x) for x in s.split(","))


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--num-workers", type=_parse_workers, default=None,
                    help="comma list, e.g. 0,1,2,4: run the multi-worker "
                         "sampling-throughput sweep")
    ap.add_argument("--sweep-steps", type=int, default=48,
                    help="timed batches per sweep configuration")
    ap.add_argument("--records-out", type=str, default=None,
                    help="write machine-readable rows here "
                         "(e.g. BENCH_pipeline.json)")
    ap.add_argument("--skip-stages", action="store_true",
                    help="only the worker sweep, skip the per-stage breakdown")
    ap.add_argument("--no-arena", action="store_true",
                    help="sweep over the legacy pickle queues instead of the "
                         "shm batch arena")
    ap.add_argument("--consumer", action="store_true",
                    help="probe the consumer completion (device-put-free "
                         "stage_from_host vs the copying reference)")
    ap.add_argument("--pin-probe", type=int, default=0, metavar="W",
                    help="probe pipeline.pin_workers on/off with W workers")
    args = ap.parse_args()
    if not args.skip_stages:
        run()
    if args.num_workers is not None:
        run_worker_sweep(steps=args.sweep_steps, workers=args.num_workers,
                         arena=not args.no_arena)
    if args.consumer:
        run_consumer_completion()
    if args.pin_probe:
        run_pinning_probe(workers=args.pin_probe)
    if args.records_out:
        write_records(args.records_out)
