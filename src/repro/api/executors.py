"""Executor registry — one protocol, three execution models.

Every way of running an HGNN training step in this repo satisfies the same
four-method protocol, so executor choice is a config string
(``RunConfig.executor``) and callers — the session, benchmarks, equivalence
tests — iterate executors uniformly:

  * ``vanilla``  — the baseline execution model: one dense parameter bundle,
    full-batch forward (``hgnn_loss``).  The correctness oracle.
  * ``raf``      — simulated multi-partition RAF (paper §4 Alg. 1): explicit
    per-partition parameter dicts, partial aggregations summed in Python.
  * ``raf_spmd`` — the production SPMD executor: relation branches stacked
    along the ``"model"`` mesh axis, learnable features updated sparsely
    through the §6 miss-penalty cache engine.

All three run every registered HGNN model (rgcn/rgat/hgt built in) through
the relation-module IR (``repro.core.relmod``, DESIGN.md §3) — executors
consume each model's declared parameter scopes and ``aggregate``, so a new
HGNN variant needs no executor changes.

Protocol (all methods take the owning :class:`repro.api.Heta` session, which
exposes graph / spec / assignment / engine / hgnn_cfg):

  ``build_plan(sess) -> plan``            static artifacts (jitted fns, plans)
  ``init_state(sess, plan) -> state``     parameters + optimizer state
  ``stage(sess, plan, batch) -> arrays``
      host-side staging: turn a :class:`SampledBatch` into the device-ready
      arrays the step consumes (table snapshot / stack / shard for the SPMD
      executor, or node ids for its compiled step to gather from a
      device cache that holds every row; ``batch_to_arrays`` for the dense
      ones).  Pure host work —
      the async pipeline (``repro.data``) runs it in a producer thread for
      batch *i+1* while batch *i* trains.
  ``step_staged(sess, plan, state, batch, arrays) -> (state, loss, step_time_s)``
      the device step on pre-staged arrays, inside the span
      ``heta.device``; ``step_time_s`` is that span's duration (compute +
      sparse update, staging excluded), so reported step times stay
      comparable with the historical ``train_hgnn`` accounting.  Its child
      spans split it (``repro.obs``): ``heta.device.wait`` (the host
      blocked on the device), ``heta.update.pull`` and ``heta.update.adam``
      (the sparse update of learnable rows).
  ``stage_reads_tables(sess, plan) -> bool``
      whether ``stage`` reads feature tables that train.  A fact, not a
      policy: the session alone decides from it and
      ``pipeline.snapshot`` whether a producer may stage ahead (see
      ``repro.data``).
  ``worker_stage_recipe(sess, plan) -> picklable | None``
      a picklable recipe with which a *sampler worker process* can perform
      the host part of ``stage`` against tables copied into the batch
      arena (``repro.data.staging.stack_batch_host``), or None when
      staging has no host part a worker could run (default; also the
      resident path).  The session hands it to the workers only where
      they may stage ahead (DESIGN.md §9/§11).
  ``stage_from_host(sess, plan, batch, host_arrays) -> arrays``
      consumer-side completion of worker staging: device placement of the
      host arrays a worker produced under the recipe; with
      ``host_arrays=None`` falls back to the full ``stage`` (the default).
      ``host_arrays`` may be read-only views into an arena slot — safe
      because the stream defers the slot release past the consuming step.
  ``loss_and_metrics(sess, plan, state, batch) -> (loss, metrics)``  eval only

Register your own with ``@executors.register("name")``.
"""

from __future__ import annotations

from types import SimpleNamespace
from typing import Dict, Tuple, Type

import numpy as np

from repro import obs

__all__ = ["Executor", "register", "get", "available", "apply_feature_grads"]

_REGISTRY: Dict[str, Type["Executor"]] = {}

#: Backends whose device arrays are host memory.  The SPMD executor keeps
#: host staging on them even over a cache that holds every row, so that CPU
#: runs of the benchmark's tiny cells read the host path's spans and
#: counters (the path a partial cache or a multi-device mesh takes on a
#: chip).  The resident gather is for an accelerator, where it spares the
#: cached rows a round trip over the host link every step.
HOST_MEMORY_PLATFORMS = frozenset({"cpu"})


def register(name: str):
    """Class decorator: ``@register("myexec")`` adds it to the registry."""

    def deco(cls: Type["Executor"]) -> Type["Executor"]:
        cls.name = name
        _REGISTRY[name] = cls
        return cls

    return deco


def get(name: str) -> "Executor":
    """Instantiate the executor registered under ``name``."""
    if name not in _REGISTRY:
        raise KeyError(
            f"unknown executor {name!r}; available: {available()}"
        )
    return _REGISTRY[name]()


def available() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


class Executor:
    """Base protocol.  Stateless: everything mutable lives in ``state``."""

    name = "?"

    def build_plan(self, sess):
        raise NotImplementedError

    def init_state(self, sess, plan):
        raise NotImplementedError

    def stage(self, sess, plan, batch):
        raise NotImplementedError

    def step_staged(self, sess, plan, state, batch, arrays):
        raise NotImplementedError

    def stage_reads_tables(self, sess, plan) -> bool:
        """True when ``stage`` reads feature tables that train, i.e.
        staging ahead of the step can observe stale rows."""
        return False

    def worker_stage_recipe(self, sess, plan):
        """Picklable host-staging recipe for sampler worker processes, or
        None when staging has no host part they could run (the default)."""
        return None

    def stage_from_host(self, sess, plan, batch, host_arrays):
        """Finish staging from worker-produced host arrays.  The base
        protocol has no worker staging, so this is the full ``stage``."""
        return self.stage(sess, plan, batch)

    def loss_and_metrics(self, sess, plan, state, batch):
        raise NotImplementedError


# --------------------------------------------------------------------------
# shared pieces
# --------------------------------------------------------------------------


def _init_full_params(sess):
    """Dense parameter bundle seeded identically across executors (the
    name-derived keys in ``init_hgnn_params`` make partition-restricted inits
    bit-identical — Prop 1)."""
    import jax

    from repro.core.hgnn import init_hgnn_params

    return init_hgnn_params(
        jax.random.PRNGKey(sess.config.run.seed), sess.hgnn_cfg, sess.spec,
        sess.feat_dims,
    )


def _engine_embed(sess):
    """Learnable tables as jnp arrays from the cache engine's authoritative
    copy, so every executor starts from the same rows."""
    import jax.numpy as jnp

    return {t: jnp.asarray(sess.engine.table(t)) for t in sess.engine.learnable_types}


# --------------------------------------------------------------------------
# vanilla — the single-bundle oracle
# --------------------------------------------------------------------------


def _lookup_tables(sess):
    """Feature tables visible to the dense executors: fixed features, plus —
    when learnable training is frozen — the engine's learnable rows as
    constants (otherwise those travel in the bundle and stay trainable)."""
    if sess.config.model.train_learnable:
        return sess.fixed_tables
    return {**sess.fixed_tables, **_engine_embed(sess)}


@register("vanilla")
class VanillaExecutor(Executor):
    def build_plan(self, sess):
        import jax

        from repro.core.hgnn import batch_to_arrays, hgnn_loss

        cfg, spec, tables = sess.hgnn_cfg, sess.spec, _lookup_tables(sess)

        def loss(bundle, arrs):
            # the oracle is float32 throughout: without this a TPU runs f32
            # matmuls in reduced precision, loosening every comparison
            # against it
            with jax.default_matmul_precision("highest"):
                return hgnn_loss(cfg, bundle, tables, arrs, spec)

        return SimpleNamespace(
            to_arrays=batch_to_arrays,
            grad=jax.jit(jax.value_and_grad(loss)),
            loss=jax.jit(loss),
        )

    def init_state(self, sess, plan):
        from repro.optim.adam import adam_init

        bundle = _init_full_params(sess)
        if sess.config.model.train_learnable:
            bundle["embed"] = _engine_embed(sess)
        return {"bundle": bundle, "opt": adam_init(bundle)}

    def stage(self, sess, plan, batch):
        with obs.span("heta.stage"):
            return plan.to_arrays(batch)

    def step_staged(self, sess, plan, state, batch, arrays):
        return _bundle_step_staged(sess, plan, state, arrays)

    def loss_and_metrics(self, sess, plan, state, batch):
        loss = float(plan.loss(state["bundle"], plan.to_arrays(batch)))
        return loss, {"loss": loss}


def _bundle_step_staged(sess, plan, state, arrs):
    """Shared dense-bundle device step on pre-staged arrays: grad + Adam
    timed — mirrors the historical step-time accounting (staging excluded)."""
    from repro.optim.adam import adam_update

    with obs.span(obs.DEVICE) as dev:
        loss, grads = plan.grad(state["bundle"], arrs)
        bundle, opt = adam_update(sess.adam_cfg, state["bundle"], grads,
                                  state["opt"])
        with obs.span("heta.device.wait"):
            loss = float(loss)
    return {"bundle": bundle, "opt": opt}, loss, dev.seconds


# --------------------------------------------------------------------------
# raf — simulated multi-partition execution (Alg. 1, explicit partitions)
# --------------------------------------------------------------------------


@register("raf")
class RafSimExecutor(Executor):
    def build_plan(self, sess):
        import jax

        from repro.core.hgnn import batch_to_arrays
        from repro.core.raf import raf_loss

        cfg, spec, tables = sess.hgnn_cfg, sess.spec, _lookup_tables(sess)
        assignment = sess.assignment
        P = assignment.num_partitions
        kernels = sess.config.kernels

        def loss(bundle, arrs):
            # one logical copy of the shared leaves (embed tables + head),
            # merged into every partition's local relation parameters
            parts = [
                {**bundle["parts"][p], "embed": bundle.get("embed", {}),
                 "head": bundle["head"]}
                for p in range(P)
            ]
            return raf_loss(cfg, parts, tables, arrs, spec, assignment, kernels)

        return SimpleNamespace(
            to_arrays=batch_to_arrays,
            grad=jax.jit(jax.value_and_grad(loss)),
            loss=jax.jit(loss),
            num_partitions=P,
        )

    def init_state(self, sess, plan):
        import jax

        from repro.core.hgnn import init_hgnn_params
        from repro.optim.adam import adam_init

        full = _init_full_params(sess)
        key = jax.random.PRNGKey(sess.config.run.seed)
        parts = [
            {k: init_hgnn_params(
                key, sess.hgnn_cfg, sess.spec, sess.feat_dims,
                restrict_rels=sess.assignment.relations_of(p, sess.spec),
            )[k] for k in ("rel", "ntype", "etype")}
            for p in range(plan.num_partitions)
        ]
        bundle = {"parts": parts, "head": full["head"]}
        if sess.config.model.train_learnable:
            bundle["embed"] = _engine_embed(sess)
        return {"bundle": bundle, "opt": adam_init(bundle)}

    def stage(self, sess, plan, batch):
        with obs.span("heta.stage"):
            return plan.to_arrays(batch)

    def step_staged(self, sess, plan, state, batch, arrays):
        return _bundle_step_staged(sess, plan, state, arrays)

    def loss_and_metrics(self, sess, plan, state, batch):
        loss = float(plan.loss(state["bundle"], plan.to_arrays(batch)))
        return loss, {"loss": loss}


# --------------------------------------------------------------------------
# raf_spmd — the production mesh executor + cache-mediated feature updates
# --------------------------------------------------------------------------


@register("raf_spmd")
class RafSpmdExecutor(Executor):
    def build_plan(self, sess):
        from repro.core import raf_spmd
        from repro.launch.mesh import make_mesh

        run = sess.config.run
        assignment = sess.assignment
        if assignment.num_partitions != run.mesh_shape[1]:
            # mesh model axis ≠ partition count: fold partitions onto shards
            # (p % shards) — meta-locality is preserved (BranchAssignment.fold)
            assignment = assignment.fold(run.mesh_shape[1], sess.spec)
        plan = raf_spmd.build_plan(sess.spec, assignment, sess.hgnn_cfg, sess.feat_dims)
        mesh = make_mesh(run.mesh_shape, ("data", "model"))
        local_combine = sess.config.partition.placement == "meta"
        learn = (bool(sess.engine.learnable_types)
                 and sess.config.model.train_learnable)
        return SimpleNamespace(
            plan=plan,
            mesh=mesh,
            learn_feats=learn,
            step=raf_spmd.make_train_step(
                plan, mesh, sess.adam_cfg, data_axes=("data",),
                local_combine=local_combine, learn_feats=learn,
                kernels=sess.config.kernels,
            ),
            loss=raf_spmd.make_loss_fn(
                plan, mesh, data_axes=("data",), local_combine=local_combine,
                kernels=sess.config.kernels,
            ),
        )

    def init_state(self, sess, plan):
        from repro.core import raf_spmd
        from repro.optim.adam import adam_init

        params = _init_full_params(sess)
        stacks = raf_spmd.shard_stacks(
            plan.plan, plan.mesh, raf_spmd.stack_params_from_dict(plan.plan, params)
        )
        return {"stacks": stacks, "opt": adam_init(stacks)}

    def _resident(self, sess, plan):
        """Each table staging reads as its cache's device rows, in node-id
        order, when every one holds its whole table and the mesh is the one
        accelerator they live on (not a backend of
        :data:`HOST_MEMORY_PLATFORMS`); else None.  Observed afresh at
        every stage and step: ``EmbedEngine.rebalance`` can change
        residency, and learnable writes replace the rows.  The
        multi-process tier's grad step (``dp_trainer``) takes staged
        features, so it keeps host staging."""
        from repro.core import raf_spmd

        if sess.config.scale.enabled or plan.mesh.devices.size != 1:
            return None
        dev = plan.mesh.devices.flat[0]
        if dev.platform in HOST_MEMORY_PLATFORMS:
            return None
        caches = sess.engine.cache.resident(
            raf_spmd.stack_recipe(plan.plan).table_types())
        if caches is None:
            return None
        if any(c.data.devices() != {dev} for c in caches.values()):
            return None
        return {t: c.data for t, c in caches.items()}

    def stage(self, sess, plan, batch):
        """Stack the batch to branch-major arrays and shard them, by one of
        two paths, chosen from what the session holds at this call:

        * resident — every table the plan reads is wholly in the device
          cache and the mesh is that one accelerator (:meth:`_resident`):
          the host writes the rows' int32 node ids
          (``staging.stack_batch_nids``) and the compiled
          step gathers the feature rows from the cache's device arrays,
          passed at step time (:meth:`_inputs`), so a rebalance or a
          learnable write is seen by the next step.  No table snapshot is
          taken.
        * host — otherwise (a partial cache, no cache, a multi-device
          mesh, the CPU backend): snapshot the tables and gather the
          feature rows on the host (``raf_spmd.stack_batch``).  Where a
          producer stages ahead while learnable tables train (the session's
          ``"stale"`` snapshot policy), the snapshot may lag the device
          step by up to ``pipeline.depth + 1`` steps
          (``stage_reads_tables`` tells the session when this applies)."""
        from repro.core import raf_spmd
        from repro.data.staging import stack_batch_nids

        resident = self._resident(sess, plan) is not None
        if not plan.learn_feats:
            # tables are static when features are frozen -> re-staging the
            # same batch (fixed-batch timing loops) would rebuild identical
            # arrays; memoize the last one
            cached = getattr(plan, "_stage_cache", None)
            if cached is not None and cached[0] is batch and cached[1] == resident:
                return cached[2]
        with obs.span("heta.stage"):
            if resident:
                host = stack_batch_nids(raf_spmd.stack_recipe(plan.plan), batch)
            else:
                host = raf_spmd.stack_batch(plan.plan, batch,
                                            sess.engine.tables_snapshot())
            arrays = raf_spmd.shard_arrays(plan.plan, plan.mesh, host)
        if not plan.learn_feats:
            plan._stage_cache = (batch, resident, arrays)
        return arrays

    def _inputs(self, sess, plan, batch, arrays) -> tuple:
        """The compiled step's arguments after the state: host-staged
        feature arrays as they are; node ids (resident staging) with each
        table's device rows as the cache holds them now.  A
        batch staged as node ids whose tables have since left the device
        (a rebalance to a partial cache) is staged again, on the host."""
        if "qnid1" not in arrays:
            return (arrays,)
        tables = self._resident(sess, plan)
        if tables is None:
            return (self.stage(sess, plan, batch),)
        return arrays, tables

    def stage_reads_tables(self, sess, plan) -> bool:
        """Host staging reads the learnable tables while they train;
        resident staging reads no table."""
        return bool(plan.learn_feats) and self._resident(sess, plan) is None

    def worker_stage_recipe(self, sess, plan):
        """On the resident path (see :meth:`stage`) None: staging only
        writes node ids and stays with the consumer, so workers only
        sample.  Otherwise the host side of :meth:`stage` — the padded
        feature gathers of ``stack_batch`` — can run inside sampler
        workers against tables copied into the batch arena; the consumer
        only device-puts.  Where those tables train
        (:meth:`stage_reads_tables`), the session passes the recipe on only
        under ``snapshot="stale"``, republishing the tables into the
        arena after every step (DESIGN.md §11)."""
        if self._resident(sess, plan) is not None:
            return None
        from repro.core import raf_spmd

        return raf_spmd.stack_recipe(plan.plan)

    def stage_from_host(self, sess, plan, batch, host_arrays):
        """Device-put-free consumer completion: the worker-staged host
        arrays (read-only arena-slot views) go straight into
        ``shard_arrays``'s sharded ``device_put`` — no intermediate
        ``jnp.asarray`` copy.  Safe against slot reuse because the stream
        defers each slot's release past the consuming step, and the step's
        ``float(loss)`` sync completes before the deferred release runs."""
        if host_arrays is None:
            return self.stage(sess, plan, batch)
        from repro.core import raf_spmd

        with obs.span("heta.stage"):
            return raf_spmd.shard_arrays(plan.plan, plan.mesh, host_arrays)

    def step_staged(self, sess, plan, state, batch, arrays):
        """Dispatch the compiled step, wait for its loss, then (learnable
        rows) pull the feature gradients and run the sparse Adam update:
        ``loss`` and the gradients come out of the same compiled step, so
        waiting for the loss first adds no sync and leaves the wait to
        ``heta.device.wait`` alone."""
        with obs.span(obs.DEVICE) as dev:
            stacks, opt, loss, *gf = plan.step(
                state["stacks"], state["opt"],
                *self._inputs(sess, plan, batch, arrays))
            with obs.span("heta.device.wait"):
                loss = float(loss)
            if plan.learn_feats:
                apply_feature_grads(sess.engine, plan.plan, batch, gf[0])
        return {"stacks": stacks, "opt": opt}, loss, dev.seconds

    def loss_and_metrics(self, sess, plan, state, batch):
        staged = self.stage(sess, plan, batch)
        loss = float(plan.loss(state["stacks"],
                               *self._inputs(sess, plan, batch, staged)))
        return loss, {"loss": loss, "hit_rates": sess.engine.cache.hit_rates()}


# --------------------------------------------------------------------------
# serve — the online inference tier (materialized embeddings, no training)
# --------------------------------------------------------------------------


@register("serve")
class ServeExecutor(Executor):
    """Score batches against the materialized embedding store (DESIGN.md §10).

    Not a training executor: ``step_staged`` raises.  ``build_plan``
    requires :meth:`Heta.infer_all` to have materialized the store;
    ``loss_and_metrics`` answers through the micro-batching
    :class:`~repro.serve.server.EmbeddingServer` (same NLL as the training
    executors), reporting per-type serve-cache hit rates."""

    def build_plan(self, sess):
        from repro.api.session import HetaStageError

        store = getattr(sess, "embedding_store", None)
        if store is None:
            raise HetaStageError(
                "the 'serve' executor requires materialized embeddings; run "
                "session.infer_all() (after compile+fit with a training "
                "executor) before compile(executor='serve')"
            )
        return SimpleNamespace(server=sess.serve(), store=store)

    def init_state(self, sess, plan):
        return {}

    def stage(self, sess, plan, batch):
        return None

    def step_staged(self, sess, plan, state, batch, arrays):
        from repro.api.session import HetaStageError

        raise HetaStageError(
            "the 'serve' executor is inference-only; train with a training "
            "executor (e.g. raf_spmd), then infer_all() + serve()"
        )

    def loss_and_metrics(self, sess, plan, state, batch):
        res = plan.server.query(batch.seeds)
        logits = res.scores.astype(np.float64)
        logits -= logits.max(axis=-1, keepdims=True)
        logp = logits - np.log(np.exp(logits).sum(axis=-1, keepdims=True))
        loss = float(-logp[np.arange(len(batch.seeds)), batch.labels].mean())
        return loss, {
            "loss": loss,
            "hit_rates": plan.server.cache.hit_rates(),
            "latency_ms": res.latency_ms,
        }


def apply_feature_grads(engine, plan, batch, gf: Dict) -> None:
    """Route gradients of the gathered feature arrays back to the learnable
    tables (paper Fig. 3 step 5, via the §6 cache)."""
    learnable = set(engine.learnable_types)
    spec = plan.spec
    k = spec.num_layers
    for d in range(1, k + 1):
        lp = plan.levels[d - 1]
        for key, types, get_ids in (
            (f"hfeat{d}", plan.src_types[d - 1], lambda b: batch.levels[d - 1].nids[b]),
            (
                f"qfeat{d}",
                plan.dst_types[d - 1],
                lambda b: (
                    batch.seeds if d == 1
                    else batch.levels[d - 2].nids[spec.levels[d - 1][b].parent]
                ),
            ),
        ):
            if key not in gf:
                continue
            with obs.span("heta.update.pull"):
                grad = np.asarray(gf[key])  # [P*rb, N, d_pad]
            grad = grad.reshape(plan.num_shards, lp.rb, *grad.shape[1:])
            per_type: Dict[str, list] = {}
            for p in range(plan.num_shards):
                for s in range(lp.rb):
                    b = lp.slot_branch[p, s]
                    if b < 0:
                        continue
                    t = types[b]
                    if t not in learnable:
                        continue
                    dim = engine.learnable_dim
                    per_type.setdefault(t, []).append(
                        (get_ids(b), grad[p, s][:, :dim])
                    )
            for t, chunks in per_type.items():
                ids = np.concatenate([c[0] for c in chunks])
                gr = np.concatenate([c[1] for c in chunks])
                engine.apply_row_grads(t, ids, gr)


# deprecated alias (pre-pipeline name); use apply_feature_grads
_apply_feature_grads = apply_feature_grads
