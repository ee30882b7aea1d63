"""``repro.api`` — the public surface of the Heta reproduction.

Quickstart
==========

One config object, one session, five explicit stages::

    from repro.api import Heta, HetaConfig, DataConfig, RunConfig

    cfg = HetaConfig(
        data=DataConfig(dataset="ogbn-mag", scale=0.01, fanouts=(10, 10),
                        batch_size=64),
        run=RunConfig(executor="raf_spmd", steps=10),
    )
    sess = Heta(cfg)

    g      = sess.build_graph()        # HetG (paper's dataset family)
    part   = sess.partition()          # §5 meta-partitioning
    print(part.metatree.render(), part.summary)
    print(sess.comm_report())          # §4: vanilla vs naive-RAF vs meta-RAF bytes
    cache  = sess.profile_and_cache()  # §6 hotness + miss-penalty cache
    sess.compile()                     # executor from the registry
    result = sess.fit()                # {"losses", "step_time_s", "hit_rates", ...}

Or collapse all stages: ``result = Heta(cfg).run()``.

Configuration
=============

:class:`HetaConfig` is a typed tree of eleven sections — ``data``,
``partition``, ``model``, ``cache``, ``run``, ``pipeline``, ``kernels``,
``serve``, ``checkpoint``, ``faults``, ``scale`` — that round-trips through
nested dicts (``to_dict``/``from_dict``), the historical flat-kwargs surface
(``from_flat_kwargs``/``to_flat_kwargs``) and auto-generated CLI flags
(``add_config_args``/``config_from_args`` — what ``python -m
repro.launch.train`` uses, so flags are derived, never duplicated).

Executors
=========

The three execution models all satisfy one staged-step protocol
(``build_plan / init_state / stage / step_staged / loss_and_metrics``, with
``step`` as the serial ``stage``+``step_staged`` composition) and are
selected by name through the registry.  The ``stage``/``step_staged`` split
is the seam ``Heta.fit`` drives through the async host pipeline
(``repro.data``) to overlap sampling + feature staging with the device
step::

    from repro.api import executors
    executors.available()                  # ("raf", "raf_spmd", "vanilla")
    cfg.with_executor("raf")               # same run, simulated-RAF executor

* ``vanilla``  — single-bundle dense baseline (the correctness oracle)
* ``raf``      — simulated multi-partition RAF, all HGNN models (§4 Alg. 1)
* ``raf_spmd`` — production SPMD executor over the (data, model) mesh
* ``serve``    — online inference tier: scores against the embeddings
  ``Heta.infer_all()`` materialized, through the micro-batching
  ``Heta.serve()`` server (``repro.serve``, DESIGN.md §10; eval-only)

Register new executors with ``@executors.register("name")``.

Deprecation
===========

``repro.launch.train.train_hgnn(...)`` — the old 18-kwarg entry point — is
now a thin wrapper over ``Heta(HetaConfig.from_flat_kwargs(...)).run()``.
New code should use the session API directly.
"""

from repro.api.config import (
    CacheConfig,
    DataConfig,
    HetaConfig,
    KernelConfig,
    ModelConfig,
    PartitionConfig,
    PipelineConfig,
    RunConfig,
    ServeConfig,
    CheckpointConfig,
    FaultConfig,
    ScaleConfig,
    add_config_args,
    config_from_args,
)
from repro.api import executors
from repro.api.session import CacheReport, Heta, HetaStageError, PartitionReport

__all__ = [
    "HetaConfig",
    "DataConfig",
    "PartitionConfig",
    "ModelConfig",
    "CacheConfig",
    "RunConfig",
    "PipelineConfig",
    "KernelConfig",
    "ServeConfig",
    "CheckpointConfig",
    "FaultConfig",
    "ScaleConfig",
    "Heta",
    "HetaStageError",
    "PartitionReport",
    "CacheReport",
    "executors",
    "add_config_args",
    "config_from_args",
]
