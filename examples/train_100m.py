"""End-to-end driver: train a ~100M-parameter HGNN for a few hundred steps.

The model is R-GAT over a Freebase-like HetG where every node type is
featureless — the ~100M parameters are dominated by the learnable feature
tables (≈1.5M nodes × 64 dims) plus per-relation attention weights, exactly
the regime Heta's cache targets (paper §2.3: learnable-feature updates are
24-35% of DGL's epoch time).

Run:  PYTHONPATH=src python examples/train_100m.py [--steps 200]
      (fit overlaps host sampling with the device step on a thread; add
      --num-workers N to feed the device from N sampler processes over the
      shared-memory graph store — DESIGN.md §9, --snapshot-policy stale to
      stage the learnable tables ahead too, bounded-stale)
"""

import argparse
import time

import numpy as np

from repro.api import (
    CacheConfig, DataConfig, Heta, HetaConfig, ModelConfig, PartitionConfig,
    PipelineConfig, RunConfig,
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch-size", type=int, default=128)
    ap.add_argument("--snapshot-policy", choices=("fresh", "stale"),
                    default="fresh",
                    help="stale: stage learnable tables ahead, bounded-stale")
    ap.add_argument("--num-workers", type=int, default=0,
                    help="sampler worker processes (0 = one thread)")
    args = ap.parse_args()

    cfg = HetaConfig(
        data=DataConfig(dataset="freebase", scale=0.001, fanouts=(10, 5),
                        batch_size=args.batch_size),
        partition=PartitionConfig(num_partitions=4),
        model=ModelConfig(model="rgat", hidden=64),
        cache=CacheConfig(cache_mb=32),
        run=RunConfig(executor="raf_spmd", steps=args.steps, log_every=10),
        pipeline=PipelineConfig(snapshot=args.snapshot_policy,
                                num_workers=args.num_workers),
    )
    sess = Heta(cfg)

    g = sess.build_graph()
    learnable_rows = sum(g.num_nodes.values())
    print(f"graph: {g.total_nodes:,} nodes / {g.total_edges:,} edges, "
          f"{len(g.relations)} relations")
    print(f"learnable parameters: {learnable_rows * 64 / 1e6:.1f}M rows×64 "
          f"(+ Adam states ×2)\n")

    t0 = time.time()
    m = sess.run()
    dt = time.time() - t0
    sess.close_pipeline()
    losses = m["losses"]
    k = max(1, len(losses) // 10)
    print(f"\nloss: first-{k}-avg {np.mean(losses[:k]):.4f} -> "
          f"last-{k}-avg {np.mean(losses[-k:]):.4f}")
    print(f"total {dt/60:.1f} min, median step {m['step_time_s']*1e3:.0f} ms")
    print(f"pipeline: {m['sampler_workers']} sampler workers, "
          f"{m['samples_per_s']:,.0f} samples/s, "
          f"overlap {m['overlap_fraction']:.2f}")
    print(f"cache hit rates: { {t: round(r, 2) for t, r in m['hit_rates'].items()} }")


if __name__ == "__main__":
    main()
