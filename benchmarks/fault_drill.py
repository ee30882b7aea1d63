"""Fault drill: recovery-time benchmarks for the DESIGN.md §12 machinery.

Three scheduled-fault drills (``repro.data.faults.FaultPlan`` — the same
deterministic coordinates the chaos test batteries use), each emitting a
recovery-time row plus the correctness flag the recovery contract
promises:

  * ``fault/worker_respawn`` — a pooled frozen-snapshot ``fit`` loses a
    sampler worker mid-run; the supervisor respawns it and replays the
    stripe.  Records the respawn downtime and whether the losses came out
    bit-identical to the undisturbed run.
  * ``fault/resume`` — interrupt a run at the midpoint checkpoint and
    resume in a fresh session.  Records save/restore wall times and
    whether the resumed tail matched the uninterrupted trajectory
    bit-for-bit.
  * ``fault/degraded_serve`` — persistent primary-path failures trip the
    serving tier's circuit breaker into the degraded direct-store path.
    Records p50 latency, trip/recovery counts, and that zero callers were
    rejected.

``--smoke`` shrinks step counts for CI; records land in
``BENCH_fault.json`` via ``write_records``.
"""

from __future__ import annotations

import argparse
import tempfile
import time

import numpy as np

from benchmarks._util import emit, write_records


def _config(steps: int, pooled: bool):
    from repro.api import (CacheConfig, DataConfig, FaultConfig, HetaConfig,
                           ModelConfig, PartitionConfig, PipelineConfig,
                           RunConfig)

    return HetaConfig(
        data=DataConfig(dataset="ogbn-mag", scale=0.002, fanouts=(3, 2),
                        batch_size=8),
        partition=PartitionConfig(num_partitions=2),
        model=ModelConfig(hidden=32),
        cache=CacheConfig(cache_mb=2, presample_epochs=1),
        run=RunConfig(executor="raf_spmd", steps=steps, lr=1e-2, seed=0),
        pipeline=PipelineConfig(num_workers=2 if pooled else 0, depth=2),
        faults=FaultConfig(max_worker_restarts=2, worker_backoff_s=0.01),
    )


def drill_worker_respawn(smoke: bool) -> None:
    from repro.api import Heta
    from repro.data.faults import FaultPlan, FaultSpec

    steps = 8 if smoke else 20
    ref = Heta(_config(steps, pooled=True)).run()

    drill = Heta(_config(steps, pooled=True))
    drill.fault_plan = FaultPlan((FaultSpec("kill_worker", step=steps // 2),))
    try:
        t0 = time.perf_counter()
        got = drill.run()
        wall = time.perf_counter() - t0
        restarts = list(drill._pool_cache[2].restarts)
    finally:
        drill.close_pipeline()
    assert len(restarts) == 1, restarts
    downtime_s = restarts[0]["downtime_s"]
    bit_identical = got["losses"] == ref["losses"]
    emit("fault/worker_respawn", downtime_s * 1e6,
         f"{'bit-identical' if bit_identical else 'DIVERGED'}, "
         f"fit {wall:.2f} s",
         kind="worker_respawn", steps=steps, kill_at=steps // 2,
         restarts=len(restarts), exitcode=restarts[0]["exitcode"],
         downtime_s=round(downtime_s, 6), fit_wall_s=round(wall, 4),
         bit_identical=bit_identical, smoke=smoke)


def drill_resume(smoke: bool) -> None:
    from repro.api import Heta
    from repro.checkpoint import latest_step

    steps = 8 if smoke else 20
    half = steps // 2
    ref = Heta(_config(steps, pooled=False)).run()["losses"]

    with tempfile.TemporaryDirectory() as d:
        first = Heta(_config(steps, pooled=False))
        first.build_graph()
        first.partition()
        first.profile_and_cache()
        first.compile()
        first.fit(half)
        t0 = time.perf_counter()
        first.save(d)
        save_s = time.perf_counter() - t0
        assert latest_step(d) == half

        resumed = Heta(_config(steps, pooled=False))
        t0 = time.perf_counter()
        resumed.restore(d)  # runs the missing stages + loads the state
        restore_s = time.perf_counter() - t0
        tail = resumed.fit(steps - half)["losses"]
    bit_identical = tail == ref[half:]
    emit("fault/resume", restore_s * 1e6,
         f"{'bit-identical' if bit_identical else 'DIVERGED'}, "
         f"save {save_s*1e3:.1f} ms",
         kind="resume", steps=steps, interrupt_at=half,
         save_s=round(save_s, 4), restore_s=round(restore_s, 4),
         bit_identical=bit_identical, smoke=smoke)


def drill_degraded_serve(smoke: bool) -> None:
    from repro.api import Heta
    from repro.data.faults import FaultPlan, FaultSpec

    sess = Heta(_config(2, pooled=False))
    sess.build_graph()
    sess.partition()
    sess.profile_and_cache()
    sess.compile()
    sess.fit()
    sess.infer_all()
    # breaker_threshold=2 failures x (1 retry + 1) attempts = 4 faults
    sess.fault_plan = FaultPlan((FaultSpec("fail_flush", step=0, count=4),))
    server = sess.serve(max_batch=8, max_wait_ms=1.0, flush_retries=1,
                        retry_backoff_ms=0.1, breaker_threshold=2,
                        breaker_cooldown_ms=100.0)
    num_requests = 16 if smoke else 64
    n = sess.graph.num_nodes[sess.graph.target_type]
    rejected = 0
    t0 = time.perf_counter()
    for k in range(num_requests):
        try:
            server.query(np.arange(k % n, min(k % n + 4, n)))
        except Exception:
            rejected += 1
    wall = time.perf_counter() - t0
    time.sleep(0.15)  # past the cooldown: the next flush is the probe
    server.query(np.arange(4))
    stats = server.stats()
    sess.close_serving()
    emit("fault/degraded_serve", stats.p50_ms * 1e3,
         f"trips {stats.breaker_trips}, degraded {stats.degraded}, "
         f"rejected {rejected}",
         kind="degraded_serve", requests=num_requests + 1,
         rejected=rejected, trips=stats.breaker_trips,
         recoveries=stats.breaker_recoveries, degraded=stats.degraded,
         retries=stats.retries, breaker_state=stats.breaker_state,
         p50_ms=round(stats.p50_ms, 4), wall_s=round(wall, 4), smoke=smoke)
    assert rejected == 0, f"{rejected} callers rejected during degradation"


def run(smoke: bool = False) -> None:
    drill_worker_respawn(smoke)
    drill_resume(smoke)
    drill_degraded_serve(smoke)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized drills (same record schema)")
    ap.add_argument("--out", default="BENCH_fault.json")
    args = ap.parse_args()
    run(smoke=args.smoke)
    write_records(args.out)


if __name__ == "__main__":
    main()
