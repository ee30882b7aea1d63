"""End-to-end Heta training driver (thin CLI over :mod:`repro.api`).

The full pipeline of the paper (Fig. 5) — synthetic HetG → meta-partitioning
(§5) → hotness + miss-penalty profiling → cache allocation (§6) → RAF
training (§4) — lives behind the :class:`repro.api.Heta` session; this module
keeps the historical entry points:

  * CLI — flags are *derived* from :class:`repro.api.HetaConfig`
    (``add_config_args``), not duplicated here::

      python -m repro.launch.train --dataset ogbn-mag --model rgcn \
          --partitions 4 --steps 100 [--mesh 2x4] [--executor raf_spmd] \
          [--placement naive] [--cache-policy hotness]

  * ``train_hgnn(...)`` — the legacy 18-kwarg programmatic entry, now a
    deprecated thin wrapper over ``Heta(HetaConfig.from_flat_kwargs(...)).run()``.
    Prefer the session API for new code.
"""

from __future__ import annotations

import argparse
import json
from typing import Dict, Optional, Sequence, Tuple

__all__ = ["train_hgnn"]


def train_hgnn(
    dataset: str = "ogbn-mag",
    scale: Optional[float] = None,
    model: str = "rgcn",
    num_partitions: int = 4,
    mesh_shape: Tuple[int, int] = (1, 1),
    batch_size: int = 32,
    fanouts: Sequence[int] = (4, 3),
    hidden: int = 64,
    steps: int = 20,
    lr: float = 5e-3,
    cache_mb: int = 4,
    hotness_only: bool = False,
    naive_placement: bool = False,
    learnable_dim: int = 64,
    seed: int = 0,
    log_every: int = 0,
    executor: str = "raf_spmd",
) -> Dict:
    """Deprecated compatibility wrapper — use :class:`repro.api.Heta`.

    Equivalent to ``Heta(HetaConfig.from_flat_kwargs(**kwargs)).run()`` and
    returns the same result keys as always (``losses``, ``step_time_s``,
    ``setup_s``, ``hit_rates``, ``partitioning``, ``meta_local``,
    ``cache_allocation``).
    """
    from repro.api import Heta, HetaConfig

    cfg = HetaConfig.from_flat_kwargs(
        dataset=dataset, scale=scale, model=model, num_partitions=num_partitions,
        mesh_shape=tuple(mesh_shape), batch_size=batch_size,
        fanouts=tuple(fanouts), hidden=hidden, steps=steps, lr=lr,
        cache_mb=cache_mb, hotness_only=hotness_only,
        naive_placement=naive_placement, learnable_dim=learnable_dim,
        seed=seed, log_every=log_every, executor=executor,
    )
    return Heta(cfg).run()


def main():
    from repro.api import Heta, add_config_args, config_from_args, executors
    from repro.launch.compile_cache import enable_compile_cache

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    add_config_args(ap)
    ap.add_argument("--naive", action="store_true",
                    help="legacy alias for --placement naive")
    ap.add_argument("--hotness-only", action="store_true",
                    help="legacy alias for --cache-policy hotness")
    ap.add_argument("--shm-cleanup", action="store_true",
                    help="sweep orphaned /dev/shm graph segments and on-disk "
                         "mmap stores left by crashed runs, then train as "
                         "usual")
    args = ap.parse_args()
    enable_compile_cache()
    if args.shm_cleanup:
        from repro.graph.mmap_store import cleanup_stale_stores
        from repro.graph.shm import cleanup_stale_segments

        removed = cleanup_stale_segments()
        print(f"shm-cleanup: removed {len(removed)} stale segment(s)"
              + ("".join(f"\n  {n}" for n in removed)))
        reaped = cleanup_stale_stores()
        print(f"shm-cleanup: removed {len(reaped)} stale mmap store(s)"
              + ("".join(f"\n  {n}" for n in reaped)))
    cfg = config_from_args(args)
    if cfg.run.executor not in executors.available():
        ap.error(f"unknown --executor {cfg.run.executor!r}; "
                 f"available: {executors.available()}")
    if args.naive:
        cfg = cfg.updated(partition=dict(placement="naive"))
    if args.hotness_only:
        cfg = cfg.updated(cache=dict(policy="hotness"))
    if args.log_every is None:
        cfg = cfg.updated(run=dict(log_every=1))
    metrics = Heta(cfg).run()
    print(json.dumps({k: v for k, v in metrics.items() if k != "losses"}, indent=1,
                     default=str))
    print(f"final loss: {metrics['losses'][-1]:.4f}")


if __name__ == "__main__":
    main()
