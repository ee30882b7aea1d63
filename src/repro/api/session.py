"""The ``Heta`` session — explicit, resumable pipeline stages.

One session wires the paper's full pipeline (Fig. 5) behind five stages,
each individually runnable and inspectable:

    sess = Heta(config)
    g      = sess.build_graph()        # HetG (synthetic dataset family)
    part   = sess.partition()          # §5 meta-partitioning -> PartitionReport
    cache  = sess.profile_and_cache()  # §6 hotness/penalty profiling -> CacheReport
    sess.compile(executor="raf_spmd")  # §4 executor via the registry
    result = sess.fit()                # train; same keys as train_hgnn

Calling a stage out of order raises :class:`HetaStageError` with the missing
prerequisite; ``run()`` executes whatever stages remain and then ``fit()``.

After training, the online inference tier (``repro.serve``, DESIGN.md §10)
hangs off two more stages: ``infer_all()`` materializes top-layer
embeddings for every node via layer-wise full-graph inference, and
``serve()`` starts the micro-batching :class:`EmbeddingServer` over the
materialized store (``close_serving()`` releases both).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
from typing import Dict, List, Optional

import numpy as np

from repro import obs
from repro.api import executors as _executors
from repro.api.config import HetaConfig

__all__ = ["Heta", "HetaStageError", "PartitionReport", "CacheReport"]


class HetaStageError(RuntimeError):
    """A lifecycle method was called before its prerequisite stage."""


@dataclasses.dataclass
class PartitionReport:
    """Inspectable result of the §5 partitioning stage."""

    summary: str
    meta_local: bool
    num_partitions: int
    metatree: object  # MetaTreeNode (render() for the figure-style tree)
    mp: object  # MetaPartitioning
    spec: object  # SampleSpec
    assignment: object  # BranchAssignment (pre-fold)

    def raf_bytes(self, batch_size: int, hidden: int, bytes_per_elem: int = 2,
                  style: str = "designated") -> int:
        """Per-batch RAF exchange bytes under this assignment (paper §4)."""
        from repro.core.raf import raf_comm_bytes

        return raf_comm_bytes(self.spec, self.assignment, batch_size, hidden,
                              bytes_per_elem, style=style)


@dataclasses.dataclass
class CacheReport:
    """Inspectable result of the §6 profiling + cache-allocation stage."""

    allocation_rows: Dict[str, int]
    learnable_types: Dict[str, int]
    hotness: object  # HotnessProfile
    penalties: object  # MissPenaltyProfile
    engine: object  # EmbedEngine


def _stage(fn):
    """A session stage, timed by its span ``heta.setup.<stage>`` into
    ``stage_times``."""
    name = fn.__name__

    @functools.wraps(fn)
    def timed(self, *args, **kw):
        with obs.span(f"heta.setup.{name}", session=self.obs_session) as sp:
            out = fn(self, *args, **kw)
        self.stage_times[name] = sp.seconds
        return out

    return timed


class Heta:
    """Session over one :class:`HetaConfig` (see module docstring)."""

    def __init__(self, config: Optional[HetaConfig] = None, **sections):
        if config is None:
            config = HetaConfig().updated(**sections) if sections else HetaConfig()
        elif sections:
            config = config.updated(**sections)
        self.config = config
        from repro.optim.adam import AdamConfig

        self.adam_cfg = AdamConfig(lr=config.run.lr)
        # the recorder's serial of this session (repro.obs): every span of
        # its stages and steps carries it
        self.obs_session = obs.new_session()
        self.stage_times: Dict[str, float] = {}  # heta.setup.<stage> spans
        # stage products
        self.graph = None
        self.hgnn_cfg = None
        self.feat_dims = None
        self.fixed_tables = None
        self.mp = None
        self.spec = None
        self.assignment = None
        self.meta_local = None
        self.engine = None
        self.executor = None
        self.plan = None
        self.state = None
        self.sampler = None
        self.losses: List[float] = []
        # per step, from its spans: the device step (heta.device) and the
        # host work before it (sampling and staging)
        self.step_times: List[float] = []
        self.host_times: List[float] = []
        # fit-loop overlap accounting (wall vs serial sum; see results())
        self._fit_wall_s = 0.0
        self._fit_serial_s = 0.0
        self._fit_steps = 0
        self._steps_done = 0
        self._queue_bytes: List[int] = []  # pooled fits: per-item queue size
        self._workers_ran = 0  # sampler processes of the last loop (0: a thread)
        # persistent sampler pool:
        # [store, arena, pool, next_global_step, workers]
        # (spawn + shm export amortize across fit() calls; see _acquire_pool)
        self._pool_cache = None
        self._pool_atexit_cb = None
        # online inference tier (repro.serve)
        self.embedding_store = None
        self._server = None
        # deterministic chaos drills (repro.data.faults.FaultPlan, or None):
        # threaded into the training pool's SampleStageTask and consumed by
        # the supervision batteries / benchmarks/fault_drill.py
        self.fault_plan = None

    # -- stage guards --------------------------------------------------------

    def _require(self, attr: str, stage: str, needed_by: str):
        if getattr(self, attr) is None:
            raise HetaStageError(
                f"{needed_by}() requires the {stage}() stage; "
                f"run session.{stage}() first (or session.run() for all stages)"
            )

    # -- stage 1: data ------------------------------------------------------

    @_stage
    def build_graph(self, graph=None):
        """Materialize the HetG and the model config derived from it.

        Pass ``graph`` to reuse a pre-built :class:`HetGraph` (sweeps over
        partition counts / fanouts, or real datasets loaded elsewhere)
        instead of synthesizing from ``DataConfig``."""
        import jax.numpy as jnp

        from repro.graph.synthetic import make_dataset

        # shm janitor (DESIGN.md §12/§13): a hard-crashed prior run can leave
        # orphaned graph/arena segments the resource tracker never saw — and,
        # since the scale-out tier, on-disk mmap stores too; sweep both kinds
        # whose owner pid is gone before allocating new ones
        try:
            from repro.graph.shm import cleanup_stale_segments

            cleanup_stale_segments()
        except Exception:
            pass  # best-effort: /dev/shm may be absent on this platform
        try:
            from repro.graph.mmap_store import cleanup_stale_stores

            cleanup_stale_stores()
        except Exception:
            pass  # best-effort: never fail session start over a sweep
        cfg = self.config
        self.graph = graph if graph is not None else make_dataset(
            cfg.data.dataset, scale=cfg.data.scale, seed=cfg.run.seed)
        self.feat_dims = {
            t: self.graph.feat_dim(t)
            for t in self.graph.num_nodes if self.graph.feat_dim(t)
        }
        self.fixed_tables = {t: jnp.asarray(f) for t, f in self.graph.features.items()}
        self.hgnn_cfg = cfg.model.to_hgnn_config(cfg.num_layers, self.graph.num_classes)
        return self.graph

    # -- stage 2: §5 meta-partitioning ---------------------------------------

    @_stage
    def partition(self) -> PartitionReport:
        """Meta-partition the graph and place relation branches."""
        from repro.core.meta_partition import meta_partition
        from repro.core.raf import assign_branches, random_branch_assignment
        from repro.graph.sampler import SampleSpec

        self._require("graph", "build_graph", "partition")
        cfg = self.config
        self.mp = meta_partition(self.graph, cfg.partition.num_partitions,
                                 num_layers=cfg.num_layers)
        self.spec = SampleSpec.from_metatree(self.mp.metatree, cfg.data.fanouts)
        self.assignment = (
            random_branch_assignment(self.spec, cfg.partition.num_partitions,
                                     seed=cfg.run.seed)
            if cfg.partition.placement == "naive"
            else assign_branches(self.spec, self.mp)
        )
        self.meta_local = self.assignment.meta_local
        return PartitionReport(
            summary=self.mp.summary(),
            meta_local=self.meta_local,
            num_partitions=cfg.partition.num_partitions,
            metatree=self.mp.metatree,
            mp=self.mp,
            spec=self.spec,
            assignment=self.assignment,
        )

    def comm_report(self, bytes_per_elem: int = 2, hidden: Optional[int] = None,
                    include_topology: bool = True) -> Dict[str, int]:
        """Per-batch communication accounting, all three execution models
        (the paper's §4 worked example: 92.3 → 8.0 → 0.5 MB).

        Returns bytes for: ``vanilla_feat`` (edge-cut feature fetching),
        ``vanilla_update`` (remote learnable-row read+write), ``raf_naive``
        (RAF, random placement) and ``raf_meta`` (RAF under the §5 meta
        placement — computed from ``assign_branches`` even when this
        session's configured placement is naive, so the comparison always
        shows the meta-partitioning gain).

        When the scale-out tier is configured (``scale.num_trainers > 1``
        or an explicit ``scale.hierarchy``), ``hier_*`` keys from
        :func:`repro.core.comm.hierarchical_comm_bytes` ride along —
        exact per-level wire bytes under the two-level hierarchical
        partition, including the Prop-2 level-0 RAF bound
        ``2(G-1)·|B|·hidden·bpe`` and the DP tier's gradient all-reduce
        bytes (DESIGN.md §13)."""
        from repro.core.comm import vanilla_comm_bytes, vanilla_update_bytes
        from repro.core.meta_partition import random_edge_cut
        from repro.core.raf import assign_branches, raf_comm_bytes, random_branch_assignment
        from repro.graph.sampler import NeighborSampler

        self._require("spec", "partition", "comm_report")
        cfg = self.config
        B = cfg.data.batch_size
        h = hidden or cfg.model.hidden
        P = cfg.partition.num_partitions
        seed = cfg.run.seed
        batch = NeighborSampler(self.graph, self.spec, B, seed=seed).sample_batch(
            self.graph.train_nodes[:B]
        )
        cut = random_edge_cut(self.graph, P, seed=seed)
        ld = cfg.model.learnable_dim
        out = {
            "vanilla_feat": vanilla_comm_bytes(
                batch, cut, self.feat_dims, learnable_dim=ld,
                bytes_per_elem=bytes_per_elem, include_topology=include_topology,
            ),
            "vanilla_update": vanilla_update_bytes(
                batch, cut, self.graph, learnable_dim=ld,
                bytes_per_elem=bytes_per_elem,
            ),
            "raf_naive": raf_comm_bytes(
                self.spec, random_branch_assignment(self.spec, P, seed=seed + 1),
                B, h, bytes_per_elem,
            ),
            "raf_meta": raf_comm_bytes(
                self.spec,
                self.assignment if self.meta_local
                else assign_branches(self.spec, self.mp),
                B, h, bytes_per_elem,
            ),
        }
        sc = cfg.scale
        if sc.enabled or sc.hierarchy is not None:
            from repro.core.comm import hierarchical_comm_bytes
            from repro.core.meta_partition import hierarchical_partition

            g, s = sc.resolved_hierarchy
            hier = hierarchical_partition(
                self.graph, g, s, num_layers=cfg.num_layers, seed=seed)
            grad_bytes = 0
            if self.state is not None:
                # DP all-reduce volume = one gradient set (= param bytes)
                import jax

                params = (self.state.get("stacks")
                          or self.state.get("bundle")) if isinstance(
                              self.state, dict) else None
                if params is not None:
                    grad_bytes = int(sum(
                        np.asarray(leaf).nbytes
                        for leaf in jax.tree_util.tree_leaves(params)))
            rep = hierarchical_comm_bytes(
                batch, hier, h, feat_dims=self.feat_dims, learnable_dim=ld,
                bytes_per_elem=bytes_per_elem, grad_bytes=grad_bytes)
            out.update({f"hier_{k}": int(v) for k, v in rep.items()})
        return out

    # -- stage 3: §6 profiling + cache ---------------------------------------

    @_stage
    def profile_and_cache(self) -> CacheReport:
        """Pre-sample hotness, profile miss penalties, allocate the cache.

        The §6 pre-sampling epochs — the same ``batch_at`` sweep training
        runs — are sampled by the session's producer
        (:meth:`_sampler_workers`): in line, or over a pool of sampler
        processes (bit-identical counts; visit counting is an
        order-independent sum)."""
        from repro.embed import EmbedEngine, profile_miss_penalties
        from repro.embed.profiler import presample_hotness_pooled

        self._require("spec", "partition", "profile_and_cache")
        cfg = self.config
        hotness = presample_hotness_pooled(
            self.graph, self.spec, cfg.data.batch_size,
            num_workers=self._sampler_workers(),
            epochs=cfg.cache.presample_epochs,
            max_batches=cfg.cache.presample_max_batches,
            seed=cfg.run.seed, depth=cfg.pipeline.depth,
        )
        penalties = profile_miss_penalties(
            self.graph, learnable_dim=cfg.model.learnable_dim,
            measured=cfg.cache.measured_penalties,
        )
        self.engine = EmbedEngine(
            self.graph, cfg.model.learnable_dim, hotness, penalties,
            cache_bytes=cfg.cache.cache_bytes, adam=self.adam_cfg,
            hotness_only=cfg.cache.hotness_only,
            num_shards=int(np.prod(cfg.run.mesh_shape)), seed=cfg.run.seed,
            kernels=cfg.kernels,
        )
        return CacheReport(
            allocation_rows=dict(self.engine.allocation.rows),
            learnable_types=dict(self.engine.learnable_types),
            hotness=hotness,
            penalties=penalties,
            engine=self.engine,
        )

    # -- stage 4: executor compilation ----------------------------------------

    @_stage
    def compile(self, executor: Optional[str] = None) -> "Heta":
        """Build the executor plan + initial state via the registry."""
        from repro.graph.sampler import NeighborSampler

        self._require("engine", "profile_and_cache", "compile")
        name = executor or self.config.run.executor
        self.executor = _executors.get(name)  # raises KeyError w/ available list
        self.plan = self.executor.build_plan(self)
        self.state = self.executor.init_state(self, self.plan)
        self.sampler = NeighborSampler(
            self.graph, self.spec, self.config.data.batch_size,
            seed=self.config.run.seed + 1,
        )
        return self

    # -- stage 5: training / evaluation ---------------------------------------

    def step(self, batch=None) -> float:
        """One optimization step (samples the next batch when none given).

        The step is the span ``heta.step``.  Its recorded step time is the
        executor's device step (span ``heta.device``: compute + sparse
        update, host staging excluded) — matching the historical
        ``train_hgnn`` accounting — and its host time, in ``host_times``,
        is the time ``heta.step`` spent before it (sampling + staging).
        The serial surface: sampling, staging and the device step in line,
        where :meth:`fit` overlaps them."""
        self._require("state", "compile", "step")
        with obs.span(obs.STEP, step=self._steps_done,
                      session=self.obs_session) as root:
            if batch is None:
                batch = self._next_batch()
            arrays = self.executor.stage(self, self.plan, batch)
            return self._consume(batch, arrays, root)

    def _consume(self, batch, arrays, root, host_s: Optional[float] = None) -> float:
        """Run the device step on pre-staged arrays inside the open step
        span ``root`` and record the books.  ``host_s`` is the host time of
        a batch staged ahead (the pipeline); on the serial path it is the
        time ``root`` spent before the executor's ``heta.device`` span."""
        self.state, loss, dt = self.executor.step_staged(
            self, self.plan, self.state, batch, arrays)
        if host_s is None:
            dev = root.last
            host_s = (dev.t0 - root.t0 if dev is not None and dev.name == obs.DEVICE
                      else root.child_s)
        self._record(loss, host_s, dt)
        return loss

    def _record(self, loss: float, host_s: float, dt: float) -> None:
        self.host_times.append(host_s)
        self.step_times.append(dt)
        self.losses.append(loss)
        self._steps_done += 1
        self._maybe_rebalance()
        self._maybe_checkpoint()

    def _maybe_rebalance(self) -> None:
        """Online §6 re-admission: every ``cache.readmit_every`` consumed
        steps, re-score cache residency from the observed access trace
        (``EmbedEngine.rebalance``).  Holds the engine's table lock, so
        it is safe against the async pipeline's producer-side fetches."""
        every = self.config.cache.readmit_every
        if every > 0 and self.engine is not None and self._steps_done % every == 0:
            self.engine.rebalance()

    def fit(self, steps: Optional[int] = None) -> Dict:
        """Train for ``steps`` (default ``RunConfig.steps``); returns the
        result dict (same keys the legacy ``train_hgnn`` returned).

        A :class:`repro.data.SampleStream` drives the loop: batch *i+1* is
        sampled and staged by the session's producer
        (:meth:`_sampler_workers`: one thread, or ``pipeline.num_workers``
        sampler processes over a shared-memory graph store and the batch
        arena, DESIGN.md §9/§11) while batch *i* trains.  The producer
        stages too where :meth:`_stages_ahead` allows it; otherwise it only
        samples and the consumer stages against the current tables.  So
        under ``snapshot="fresh"`` (the default) batches, staged arrays and
        losses are bit-identical to a loop of :meth:`step`; only when the
        work happens changes.  ``snapshot="stale"`` stages learnable
        tables ahead too, bounded-stale.  A pool, its store and arena
        persist across consecutive ``fit()`` calls (spawn cost amortizes;
        see :meth:`close_pipeline`) and are torn down on error.  Batches
        are bit-identical for any worker count (per-batch RNG)."""
        self._require("state", "compile", "fit")
        steps = self.config.run.steps if steps is None else steps
        if steps and self.config.scale.enabled:
            # multi-process data-parallel tier (DESIGN.md §13): rank 0 is
            # this process; scale.num_trainers-1 trainer processes attach
            # the shared store and the loop runs in repro.data.dp_trainer
            from repro.data.dp_trainer import run_dp_fit

            return run_dp_fit(self, steps)
        log_every = self.config.run.log_every

        def logged(loss: float) -> None:
            i = self._steps_done - 1
            if log_every and i % log_every == 0:
                print(f"step {i:4d} loss {loss:.4f} "
                      f"({self.step_times[-1]*1e3:.1f} ms)")

        t_wall = time.perf_counter()
        n0 = len(self.step_times)
        if steps:
            from repro.data.sample_stream import SampleStream

            start = self._steps_done
            pool = arena = None
            if self._sampler_workers():
                pool, arena = self._acquire_pool(start)
            # learnable-"stale" worker staging: after every consumed step,
            # republish the updated learnable tables into the arena's
            # seqlock'd region so workers stage batch i+k against tables at
            # most the ring depth behind the trainer (DESIGN.md §11)
            republish = arena is not None and arena.handle.tables_mutable
            try:
                with SampleStream(
                    lambda i: self._batch_for_step(start + i),
                    lambda b: self.executor.stage(self, self.plan, b),
                    num_steps=steps, depth=self.config.pipeline.depth,
                    defer_stage=not self._stages_ahead(),
                    pool=pool, arena=arena, spec=self.spec,
                    finish_stage=lambda b, host: self.executor.stage_from_host(
                        self, self.plan, b, host),
                    first_step=start, session=self.obs_session,
                ) as stream:
                    for batch, arrays, host_s in stream:
                        with obs.span(obs.STEP, step=self._steps_done,
                                      session=self.obs_session) as root:
                            loss = self._consume(batch, arrays, root, host_s)
                        logged(loss)
                        if pool is not None:
                            self._pool_cache[3] += 1  # pool stays in sync
                        if republish:
                            arena.publish_tables({
                                t: self.engine.table(t)
                                for t in self.engine.learnable_types
                            })
                    self._queue_bytes.extend(stream.queue_bytes)
            except BaseException:
                # a failed pooled fit leaves pool position and _steps_done
                # out of sync (and possibly dead workers): tear down so the
                # next fit starts a fresh, aligned pool
                self.close_pipeline()
                raise
        self._fit_wall_s += time.perf_counter() - t_wall
        self._fit_steps += len(self.step_times) - n0
        self._fit_serial_s += sum(self.host_times[n0:]) + sum(self.step_times[n0:])
        return self.results()

    def evaluate(self, num_batches: int = 1, use_full_graph: bool = False) -> Dict:
        """Mean held-out-batch loss via the executor's eval path (no update).

        Batches are prefetched in the background by the session's producer
        (:meth:`_sampler_workers`): a thread, or ``pipeline.num_workers``
        sampler processes over a shared-memory graph store (eval trains no
        table, so any producer is bit-exact).

        ``use_full_graph=True`` scores the *same* held-out batches against
        the embeddings :meth:`infer_all` materialized instead of running the
        executor's sampled forward — identical numbers when sampling is
        exhaustive (fanouts >= max in-degree; see ``repro.serve``)."""
        from repro.graph.sampler import NeighborSampler

        self._require("state", "compile", "evaluate")
        sampler = NeighborSampler(
            self.graph, self.spec, self.config.data.batch_size,
            seed=self.config.run.seed + 9999,
        )
        eval_seed = self.config.run.seed + 9999
        n = min(num_batches, sampler.steps_per_epoch())
        losses, metrics = [], {}

        if use_full_graph:
            self._require("embedding_store", "infer_all",
                          "evaluate(use_full_graph=True)")
            it = sampler.epoch(shuffle=True, seed=eval_seed)
            for _ in range(n):
                b = next(it)
                logits = self.embedding_store.scores(b.seeds)
                logits = logits.astype(np.float64)
                logits -= logits.max(axis=-1, keepdims=True)
                logp = logits - np.log(
                    np.exp(logits).sum(axis=-1, keepdims=True))
                losses.append(float(
                    -logp[np.arange(len(b.seeds)), b.labels].mean()))
            return {"loss": float(np.mean(losses)),
                    "num_batches": len(losses), "full_graph": True}

        def consume(b):
            loss, m = self.executor.loss_and_metrics(self, self.plan,
                                                     self.state, b)
            losses.append(loss)
            return m

        from repro.data.sample_stream import SampleStream
        from repro.data.worker_pool import EpochSchedule, WorkerPool

        depth = self.config.pipeline.depth
        with contextlib.ExitStack() as owned:
            pool = arena = None
            if self._sampler_workers():
                store, arena, task = self._pool_task(
                    EpochSchedule(eval_seed, sampler.steps_per_epoch()),
                    eval_seed,
                )
                owned.callback(arena.unlink)
                owned.callback(store.unlink)
                pool = owned.enter_context(WorkerPool(
                    task, num_workers=self.config.pipeline.num_workers,
                    depth=depth, num_items=n, name="eval-pool",
                    **self._supervision_kw(arena)))
            # eval consumes raw batches: no staging on either side
            with SampleStream(
                lambda i: sampler.batch_at(i, epoch_seed=eval_seed),
                lambda b: None, num_steps=n, depth=depth,
                pool=pool, arena=arena, spec=self.spec,
                finish_stage=lambda b, host: None,
            ) as stream:
                for b, _, _ in stream:
                    metrics = consume(b)
        return {"loss": float(np.mean(losses)), "num_batches": len(losses),
                **{k: v for k, v in metrics.items() if k != "loss"}}

    # -- stage 6: the online inference tier (repro.serve) ----------------------

    @_stage
    def infer_all(self, node_block: Optional[int] = None,
                  shm: Optional[bool] = None):
        """Materialize top-layer embeddings for every node of every type via
        layer-wise full-graph inference (DESIGN.md §10), from the trained
        SPMD stacks.  ``node_block``/``shm`` default to ``ServeConfig``.
        Returns (and parks on the session) the
        :class:`~repro.serve.full_graph.EmbeddingStore`."""
        from repro.serve.full_graph import infer_all as _infer_all

        self._require("state", "compile", "infer_all")
        plan = getattr(self.plan, "plan", None)
        stacks = self.state.get("stacks") if isinstance(self.state, dict) else None
        if plan is None or stacks is None:
            raise HetaStageError(
                f"infer_all() needs the stacked SPMD plan, but executor "
                f"{self.executor.name!r} does not expose one; "
                "compile(executor='raf_spmd') first"
            )
        scfg = self.config.serve
        store = _infer_all(
            self.graph, plan, stacks, self.engine.tables_snapshot(),
            node_block=scfg.node_block if node_block is None else node_block,
            kernels=self.config.kernels,
            shm=scfg.shm if shm is None else shm,
        )
        if self.embedding_store is not None:
            self.close_serving()
        self.embedding_store = store
        return store

    def serve(self, **overrides):
        """Start (or return) the micro-batching
        :class:`~repro.serve.server.EmbeddingServer` over the materialized
        store.  Flush policy / cache budget come from ``ServeConfig``
        (keyword overrides win); the scoring step runs on
        ``make_production_mesh`` when ``serve.production_mesh`` is set, else
        on the run's mesh.  ``close_serving()`` stops it."""
        if self._server is not None:
            return self._server
        self._require("embedding_store", "infer_all", "serve")
        from repro.serve.server import EmbeddingServer

        scfg = self.config.serve
        if scfg.production_mesh:
            from repro.launch.mesh import make_production_mesh

            mesh = make_production_mesh()
        else:
            mesh = getattr(self.plan, "mesh", None)
        kw = dict(
            max_batch=scfg.max_batch, max_wait_ms=scfg.max_wait_ms,
            max_queue=scfg.max_queue, cache_mb=scfg.cache_mb,
            kernels=self.config.kernels, mesh=mesh,
            readmit_every=scfg.readmit_every,
            deadline_ms=scfg.deadline_ms,
            flush_retries=scfg.flush_retries,
            retry_backoff_ms=scfg.retry_backoff_ms,
            breaker_threshold=scfg.breaker_threshold,
            breaker_cooldown_ms=scfg.breaker_cooldown_ms,
            faults=self.fault_plan,
        )
        kw.update(overrides)
        self._server = EmbeddingServer(self.embedding_store, **kw)
        return self._server

    def close_serving(self) -> None:
        """Stop the embedding server and release the store (unlinking its
        shm segment when shm-backed).  Idempotent."""
        srv, self._server = self._server, None
        if srv is not None:
            srv.close()
        store, self.embedding_store = self.embedding_store, None
        if store is not None:
            store.close()

    # -- convenience -----------------------------------------------------------

    def run(self) -> Dict:
        """Execute whatever stages remain, then ``fit()``."""
        if self.graph is None:
            self.build_graph()
        if self.spec is None:
            self.partition()
        if self.engine is None:
            self.profile_and_cache()
        if self.state is None:
            self.compile()
        return self.fit()

    # -- checkpoint / resume (DESIGN.md §12) ------------------------------------

    def config_fingerprint(self) -> str:
        """sha256 over the canonical config dict — stamped into every
        checkpoint manifest so :meth:`restore` refuses state trained under
        a different configuration."""
        import hashlib
        import json

        blob = json.dumps(self.config.to_dict(), sort_keys=True, default=str)
        return hashlib.sha256(blob.encode()).hexdigest()

    def _ckpt_tree(self) -> Dict:
        """The checkpointable pytree: executor state (param stacks +
        optimizer), learnable embed tables + Adam rows + step counters,
        readmission EMA, and the cache-residency profile."""
        snap = self.engine.state_snapshot()
        return {
            "state": self.state,
            "embed": {
                "tables": snap["tables"],
                "m": snap["m"],
                "v": snap["v"],
                "steps": {t: np.int64(s) for t, s in snap["steps"].items()},
                "hotness_ema": snap["hotness_ema"],
                "residency": {t: np.asarray(ids, np.int64)
                              for t, ids in snap["residency"].items()},
            },
        }

    def save(self, directory: Optional[str] = None, name: str = "ckpt") -> str:
        """Atomically checkpoint the full session state at the current step.

        Written via :func:`repro.checkpoint.save_checkpoint` (npz tmp +
        rename, then manifest rename as the commit point; per-array sha256
        hashes).  The manifest's ``extra`` records the config fingerprint,
        the sampler position ``(steps_done, epoch_seed, step_in_epoch)``
        and the run seed, so :meth:`restore` resumes the loss trajectory
        bit-for-bit.  ``directory`` defaults to ``checkpoint.dir``."""
        from repro.checkpoint import save_checkpoint

        self._require("state", "compile", "save")
        directory = directory or self.config.checkpoint.dir
        if directory is None:
            raise ValueError(
                "save() needs a directory (argument or checkpoint.dir config)")
        step = self._steps_done
        epoch_seed, idx = self._schedule().seed_and_index(step)
        extra = {
            "fingerprint": self.config_fingerprint(),
            "steps_done": step,
            "epoch_seed": int(epoch_seed),
            "step_in_epoch": int(idx),
            "seed": int(self.config.run.seed),
        }
        path = save_checkpoint(directory, step, self._ckpt_tree(),
                               name=name, extra=extra)
        self._prune_checkpoints(directory, name)
        return path

    def restore(self, directory: Optional[str] = None,
                step: Optional[int] = None, name: str = "ckpt") -> int:
        """Load a committed checkpoint and position the session at its step.

        Runs any missing pipeline stages first (the restored arrays load
        into freshly-compiled templates), verifies the config fingerprint
        and every array's content hash (:class:`CheckpointError` on any
        mismatch or torn write), and realigns the sampler so the next
        ``fit``/``step`` continues the interrupted run's loss trajectory
        bit-for-bit.  Returns the restored step."""
        from repro.checkpoint import (CheckpointError, latest_step,
                                      load_checkpoint, read_manifest)

        directory = directory or self.config.checkpoint.dir
        if directory is None:
            raise ValueError(
                "restore() needs a directory (argument or checkpoint.dir)")
        if step is None:
            step = latest_step(directory, name)
            if step is None:
                raise CheckpointError(
                    f"no committed checkpoint found in {directory!r}")
        if self.graph is None:
            self.build_graph()
        if self.spec is None:
            self.partition()
        if self.engine is None:
            self.profile_and_cache()
        if self.state is None:
            self.compile()
        manifest = read_manifest(directory, step, name)
        extra = manifest.get("extra", {})
        fp = extra.get("fingerprint")
        if fp and fp != self.config_fingerprint():
            raise CheckpointError(
                f"checkpoint at step {step} was written under a different "
                f"HetaConfig (fingerprint {fp[:12]}… != "
                f"{self.config_fingerprint()[:12]}…)")
        template = self._ckpt_tree()
        # residency sets change size across rebalances: template shapes for
        # them come from the manifest, not from the session's current cache
        template["embed"]["residency"] = {
            key.split("/", 2)[2]: np.zeros(tuple(manifest["shapes"][key]),
                                           np.int64)
            for key in manifest.get("keys", [])
            if key.startswith("embed/residency/")
        }
        tree = load_checkpoint(directory, step, template, name=name)
        self.state = tree["state"]
        emb = tree["embed"]
        self.engine.load_state({
            "tables": emb["tables"],
            "m": emb["m"],
            "v": emb["v"],
            "steps": {t: int(s) for t, s in emb["steps"].items()},
            "hotness_ema": emb["hotness_ema"],
            "residency": emb["residency"],
        })
        self._steps_done = int(extra.get("steps_done", step))
        # the persistent pool (if any) is positioned at the pre-restore
        # step; tear it down so the next fit respawns aligned
        self.close_pipeline()
        return step

    def _maybe_checkpoint(self) -> None:
        """Periodic checkpointing: every ``checkpoint.every_steps`` consumed
        steps, :meth:`save` to ``checkpoint.dir`` (both config-driven)."""
        c = self.config.checkpoint
        if (c.every_steps > 0 and self._steps_done > 0
                and self._steps_done % c.every_steps == 0):
            self.save(c.dir)

    def _prune_checkpoints(self, directory: str, name: str) -> None:
        """Keep only the newest ``checkpoint.keep`` committed checkpoints
        (0 = keep everything)."""
        import os
        import re

        keep = self.config.checkpoint.keep
        if keep <= 0:
            return
        steps = sorted(
            int(m.group(1))
            for f in os.listdir(directory)
            if (m := re.fullmatch(rf"{name}_(\d+)\.npz", f))
            and os.path.exists(os.path.join(directory, f + ".json"))
        )
        for s in steps[:-keep]:
            base = os.path.join(directory, f"{name}_{s:08d}.npz")
            for p in (base, base + ".json"):
                try:
                    os.remove(p)
                except OSError:
                    pass

    def results(self) -> Dict:
        """The legacy ``train_hgnn`` result dict."""
        self._require("engine", "profile_and_cache", "results")
        # exclude jit-compile warmup from the reported step time
        timed = (self.step_times[2:] if len(self.step_times) > 4
                 else self.step_times) or [0.0]
        setup = sum(self.stage_times.values())
        # overlap fraction: share of serial host+device work hidden by the
        # pipeline (0 when serial: wall >= host + step by construction)
        serial = self._fit_serial_s
        overlap = max(0.0, 1.0 - self._fit_wall_s / serial) if serial > 0 else 0.0
        # seeds consumed per second of fit() wall time — the host-pipeline
        # throughput figure the worker-pool benchmarks sweep
        samples_per_s = (
            self._fit_steps * self.config.data.batch_size / self._fit_wall_s
            if self._fit_wall_s > 0 else 0.0
        )
        return {
            "losses": list(self.losses),
            "step_time_s": float(np.median(timed)),
            "host_time_s": float(np.median(self.host_times or [0.0])),
            "setup_s": setup,
            "sampler_workers": self._workers_ran,
            "samples_per_s": float(samples_per_s),
            "overlap_fraction": float(overlap),
            # mean pickled bytes per worker→consumer queue item: ~1e2, the
            # batch arena's SlotRef descriptors
            "queue_bytes_per_step": (
                float(np.mean(self._queue_bytes)) if self._queue_bytes
                else 0.0),
            "hit_rates": self.engine.cache.hit_rates(),
            "partitioning": self.mp.summary(),
            "meta_local": self.meta_local,
            "cache_allocation": dict(self.engine.allocation.rows),
            "executor": self.executor.name if self.executor else None,
            # per span name of this session: count, seconds, self seconds
            # (repro.obs), and the counters its spans kept
            "spans": obs.totals(self.obs_session),
            "counters": obs.counters(self.obs_session),
        }

    # -- internal ---------------------------------------------------------------

    def _schedule(self, start_step: int = 0):
        """The epoch schedule of the training loop: epoch ``e`` starts at
        step ``e * steps_per_epoch`` and shuffles with the seed the legacy
        epoch-iterator used at that boundary (``run.seed + 2 +
        first_step_of_epoch``).  One shared object — serial loop, thread
        stream and every pool worker all derive batches from it."""
        from repro.data.worker_pool import EpochSchedule

        E = self.sampler.steps_per_epoch()
        if E == 0:
            raise ValueError(
                f"batch_size ({self.config.data.batch_size}) exceeds the "
                f"number of train nodes ({len(self.graph.train_nodes)})"
            )
        return EpochSchedule(self.config.run.seed + 2, E,
                             start_step=start_step)

    def _batch_for_step(self, s: int):
        """The training batch of global step ``s`` — a pure function of
        ``(config seed, s)``, so the serial loop and the async stream (which
        materializes batches ahead, possibly out of thread or out of
        process) see identical data."""
        with obs.span("heta.sample", step=s, session=self.obs_session):
            epoch_seed, i = self._schedule().seed_and_index(s)
            return self.sampler.batch_at(i, epoch_seed=epoch_seed)

    def _sampler_workers(self) -> int:
        """The producer of the session's sampling loops — :meth:`fit`,
        :meth:`evaluate` and :meth:`profile_and_cache` all ask here: a pool
        of ``pipeline.num_workers`` sampler processes over a shared-memory
        graph store where that is > 0, else 0, one thread.  What it
        answers is what ``results()["sampler_workers"]`` reports."""
        self._workers_ran = self.config.pipeline.num_workers
        return self._workers_ran

    def _stages_ahead(self) -> bool:
        """Whether a producer, thread or sampler worker, may stage a batch
        ahead of its step: where staging reads no table that trains, or
        under ``pipeline.snapshot="stale"`` (bounded-stale tables).  The
        one place the session decides staleness."""
        return (self.config.pipeline.snapshot == "stale"
                or not self.executor.stage_reads_tables(self, self.plan))

    def _acquire_pool(self, start_step: int):
        """The persistent sampler pool positioned at ``start_step``.

        Spawning workers and exporting the shm store cost ~a second; one
        pool therefore serves consecutive ``fit()`` calls as long as the
        requested start lines up with where the pool's stripe left off
        (tracked in ``_pool_cache``) and the worker count is unchanged.
        Misalignment — a serial ``step()`` in between, a config change, a
        prior failure — tears the old pool down and spawns a fresh one.
        ``close_pipeline()`` (also invoked on fit errors) releases
        everything explicitly; GC of the session is the fallback."""
        from repro.data.worker_pool import WorkerPool

        pcfg = self.config.pipeline
        if self._pool_cache is not None:
            store, arena, pool, next_step, workers = self._pool_cache
            if (workers == pcfg.num_workers and next_step == start_step
                    and not pool._closed):
                return pool, arena
            self.close_pipeline()
        recipe = (self.executor.worker_stage_recipe(self, self.plan)
                  if self._stages_ahead() else None)
        store, arena, task = self._pool_task(
            self._schedule(start_step), self.config.run.seed + 1,
            recipe=recipe, faults=self.fault_plan,
        )
        pool = WorkerPool(task, num_workers=pcfg.num_workers,
                          depth=pcfg.depth, num_items=None,
                          **self._supervision_kw(arena))
        self._pool_cache = [store, arena, pool, start_step, pcfg.num_workers]
        if self._pool_atexit_cb is None:
            # scripts that train and simply exit must not leave the store
            # to the resource tracker's leaked-segment shutdown path (it
            # cleans up, but warns); weakref so the hook never pins the
            # session alive
            import atexit
            import weakref

            ref = weakref.ref(self)

            def _cleanup(_ref=ref):
                sess = _ref()
                if sess is not None:
                    sess.close_pipeline()

            atexit.register(_cleanup)
            self._pool_atexit_cb = _cleanup
        return pool, arena

    def close_pipeline(self) -> None:
        """Tear down the persistent sampler pool and unlink its shm store.

        Idempotent; safe to call any time.  Sessions that ran pooled fits
        release their workers and segments here (or implicitly at GC)."""
        cb, self._pool_atexit_cb = self._pool_atexit_cb, None
        if cb is not None:
            import atexit

            try:  # don't accumulate dead hooks across many sessions
                atexit.unregister(cb)
            except Exception:
                pass
        if self._pool_cache is None:
            return
        store, arena, pool, _, _ = self._pool_cache
        self._pool_cache = None
        try:
            pool.close()
        finally:
            try:
                store.unlink()
            finally:
                arena.unlink()

    def _supervision_kw(self, arena) -> Dict:
        """WorkerPool supervision kwargs from ``FaultConfig`` (DESIGN.md
        §12): restart budget, backoff, and the death hook that poisons the
        dead worker's arena sub-ring so stale ``SlotRef``\\ s fail loudly
        before the replacement replays the stripe."""
        fcfg = self.config.faults
        return dict(max_restarts=fcfg.max_worker_restarts,
                    restart_backoff_s=fcfg.worker_backoff_s,
                    on_worker_death=arena.invalidate_worker_slots)

    def _pool_task(self, schedule, sampler_seed: int, recipe=None,
                   faults=None):
        """Shared-memory graph store, batch arena and picklable sampling
        task for a worker pool following ``schedule`` (the caller owns
        both: ``_acquire_pool`` parks them in ``_pool_cache``, ``evaluate``
        unlinks per call).  Staging moves into the workers when the
        executor provides a ``recipe`` — exactly the tables its branches
        read travel with the batch pipeline; with ``recipe=None`` workers
        sample only and staging stays consumer-side.

        Batches flow through a fixed-slot shm ring buffer, the batch arena
        (DESIGN.md §11): the tables live in the arena segment —
        seqlock-republishable when learnable tables train under the
        ``"stale"`` policy — and the queues carry only :class:`SlotRef`
        descriptors."""
        from repro.data.staging import arena_fields
        from repro.data.worker_pool import SampleStageTask
        from repro.graph.shm import create_arena, share_graph

        pcfg = self.config.pipeline
        tables = None
        if recipe is not None:
            snapshot = self.engine.tables_snapshot()
            tables = {t: snapshot[t] for t in recipe.table_types()}
        store = share_graph(self.graph, include_features=False)
        probe = self._batch_for_step(0)  # padded shapes: any step works
        mutable = (recipe is not None
                   and bool(getattr(self.plan, "learn_feats", False)))
        arena = create_arena(
            arena_fields(probe, recipe=recipe, tables=tables),
            num_workers=pcfg.num_workers, depth=pcfg.depth,
            tables=tables, tables_mutable=mutable,
        )
        task = SampleStageTask(
            handle=store.handle,
            spec=self.spec,
            batch_size=self.config.data.batch_size,
            sampler_seed=sampler_seed,
            schedule=schedule,
            recipe=recipe,
            arena=arena.handle,
            faults=faults,
            write_timeout_s=self.config.faults.arena_write_timeout_s,
        )
        return store, arena, task

    def _next_batch(self):
        return self._batch_for_step(self._steps_done)
