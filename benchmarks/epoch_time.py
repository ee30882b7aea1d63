"""Paper Fig. 8/9 — end-to-end epoch time, Heta vs the vanilla execution
model.

Two readings:
  * measured — actual per-step wall time of the SPMD executor on this CPU
    host for Heta (meta placement) vs the naive-placement ablation (the
    communication difference shows up as extra work in the inner psum).
    Driven through the session API with a fixed batch and learnable-feature
    training frozen (``ModelConfig(train_learnable=False)``), so the timed
    region is the jitted device step alone — the same quantity the
    pre-session-API benchmark measured (host staging and the cache's sparse
    write-back are measured separately in breakdown.py).
  * projected — the α-β model over exact per-batch byte counts at the
    paper's testbed constants (100 Gbps, PCIe3), giving the epoch-time
    split the paper measures on 2×g4dn.metal.  Heta's speedup there comes
    from eliminating feature fetching + remote learnable updates.
"""

from __future__ import annotations

import numpy as np

from benchmarks._util import dram_random_time, emit, net_time, timed_fit
from repro.api import (
    CacheConfig, DataConfig, Heta, HetaConfig, ModelConfig, PartitionConfig,
    RunConfig,
)


def projected_epoch(dataset: str, scale, batch: int, fanouts, hidden: int = 64):
    """α-β projection of one epoch's comm/update time, vanilla vs Heta."""
    sess = Heta(HetaConfig(
        data=DataConfig(dataset=dataset, scale=scale, fanouts=fanouts,
                        batch_size=batch),
        partition=PartitionConfig(num_partitions=2),
        model=ModelConfig(hidden=hidden),
    ))
    g = sess.build_graph()
    sess.partition()
    comm = sess.comm_report(bytes_per_elem=2)
    steps = max(1, len(g.train_nodes) // batch)

    t_vanilla = steps * (net_time(comm["vanilla_feat"], 64)
                         + net_time(comm["vanilla_update"], 16)
                         + dram_random_time(comm["vanilla_update"]))
    t_heta = steps * net_time(comm["raf_meta"], 4)
    return t_vanilla, t_heta, steps


def _measured_step(model: str, local: bool) -> float:
    """Warm, fixed-batch device step time of the SPMD executor through the
    session (learnable features frozen: device compute only, as before)."""
    sess = Heta(HetaConfig(
        data=DataConfig(dataset="ogbn-mag", scale=0.002, fanouts=(5, 4),
                        batch_size=32),
        partition=PartitionConfig(num_partitions=2,
                                  placement="meta" if local else "naive"),
        model=ModelConfig(model=model, hidden=64, train_learnable=False),
        cache=CacheConfig(cache_mb=2),
        run=RunConfig(executor="raf_spmd", mesh_shape=(1, 1), seed=1),
    ))
    g = sess.build_graph()
    sess.partition()
    sess.profile_and_cache()
    sess.compile()
    batch = sess.sampler.sample_batch(g.train_nodes[:32])
    for _ in range(6):
        sess.step(batch)
    return float(np.median(sess.step_times[2:]))


def _measured_fit(pipelined: bool, steps: int = 16,
                  num_workers: int = 0) -> tuple:
    """End-to-end wall time per step, a ``Heta.step()`` loop (host stages
    in line) vs ``Heta.fit`` (sample+stage overlapped) — identical batches
    either way (per-batch sampler RNG), so the difference is purely the
    sample+stage work hidden behind the device step.  On a
    CPU-only host the win is modest (the producer shares cores + the GIL
    with the jitted step); the breakdown benchmark reports the overlap
    fraction the stream actually achieved.  ``num_workers`` selects the
    producer: the background thread (0) or a sampler process pool that also
    stages frozen-table batches worker-side (DESIGN.md §9)."""
    cfg = HetaConfig(
        data=DataConfig(dataset="ogbn-mag", scale=0.002, fanouts=(5, 4),
                        batch_size=32),
        partition=PartitionConfig(num_partitions=2),
        model=ModelConfig(hidden=64, train_learnable=False),
        cache=CacheConfig(cache_mb=2),
        run=RunConfig(executor="raf_spmd", mesh_shape=(1, 1), seed=1,
                      steps=steps),
    )
    if num_workers:
        cfg = cfg.updated(pipeline=dict(num_workers=num_workers))
    sess = Heta(cfg)
    sess.build_graph()
    sess.partition()
    sess.profile_and_cache()
    sess.compile()
    try:
        # warmup inside timed_fit spawns the pool; the timed fit reuses it,
        # so the figure is steady-state, not worker spawn cost
        wall, overlap = timed_fit(sess, steps, serial=not pipelined)
        return wall, overlap, sess.results()["queue_bytes_per_step"]
    finally:
        sess.close_pipeline()


def run_worker_fit_sweep(workers=(0, 1, 2, 4), steps: int = 16):
    """End-to-end fit per-step wall time across sampler worker counts —
    same model, same batches (bit-identical for any worker count); emits
    machine-readable rows for ``BENCH_pipeline.json``."""
    import os

    t_serial, _, _ = _measured_fit(pipelined=False, steps=steps)
    emit("pipeline/fit/serial_step", t_serial * 1e6, "Heta.step loop",
         workers=-1, kind="fit", batch_size=32,
         queue_bytes_per_step=0, cpus=os.cpu_count())
    for w in workers:
        t_w, overlap, qbytes = _measured_fit(pipelined=True, steps=steps,
                                             num_workers=w)
        emit(f"pipeline/fit/workers{w}", t_w * 1e6,
             f"overlap {overlap:.2f}, {t_serial / max(t_w, 1e-12):.2f}x vs "
             f"serial, {qbytes:.0f} B/queue item",
             workers=w, kind="fit", batch_size=32,
             samples_per_s=round(32 / max(t_w, 1e-12), 1),
             overlap_fraction=round(overlap, 3),
             speedup_vs_serial=round(t_serial / max(t_w, 1e-12), 3),
             queue_bytes_per_step=round(qbytes, 1),
             cpus=os.cpu_count())


def run():
    # measured: warm step time of the real executor, meta vs naive placement
    for model in ("rgcn", "rgat"):
        t_meta = _measured_step(model, local=True)
        t_naive = _measured_step(model, local=False)
        emit(f"epoch/measured/{model}/heta_step", t_meta * 1e6, "meta placement")
        emit(f"epoch/measured/{model}/naive_step", t_naive * 1e6,
             "naive placement (adds inner-level exchange; ~equal on 1 device)")

    # ablation: a Heta.step loop vs the overlapped Heta.fit (same batches)
    t_serial, _, _ = _measured_fit(pipelined=False)
    t_pipe, overlap, _ = _measured_fit(pipelined=True)
    emit("epoch/pipeline/serial_step", t_serial * 1e6, "host stages in line")
    emit("epoch/pipeline/overlapped_step", t_pipe * 1e6,
         f"sample+stage prefetched; overlap fraction {overlap:.2f}")
    emit("epoch/pipeline/speedup", t_serial / max(t_pipe, 1e-12),
         "serial / overlapped wall per step")

    # projected at the paper's constants (comm+update portion of the epoch)
    for ds, scale, batch in (("ogbn-mag", 0.01, 1024), ("mag240m", 0.0005, 1024)):
        tv, th, steps = projected_epoch(ds, scale, batch, (25, 20))
        emit(f"epoch/projected/{ds}/vanilla", tv * 1e6, f"{steps} steps/epoch")
        emit(f"epoch/projected/{ds}/heta", th * 1e6,
             f"comm speedup {tv/max(th,1e-12):.1f}x (paper e2e: 1.9-5.8x incl. compute)")
    return True


if __name__ == "__main__":
    import argparse

    from benchmarks._util import write_records

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--num-workers", default=None,
                    help="comma list, e.g. 0,1,2,4: sweep sampler worker "
                         "counts through an end-to-end fit")
    ap.add_argument("--records-out", type=str, default=None,
                    help="write machine-readable rows here")
    ap.add_argument("--skip-main", action="store_true",
                    help="only the worker sweep, skip the epoch-time runs")
    args = ap.parse_args()
    if not args.skip_main:
        run()
    if args.num_workers is not None:
        run_worker_fit_sweep(
            workers=tuple(int(x) for x in str(args.num_workers).split(",")))
    if args.records_out:
        write_records(args.records_out)
