"""SPMD RAF executor — relations laid along the ``"model"`` mesh axis.

This is the production realization of paper Alg. 1 on a TPU mesh:

  * the metatree's branches are grouped by owning meta-partition and the
    branch axis is sharded over ``"model"`` — each model-shard holds its
    partition's relation parameters, sampled blocks and feature slices;
  * relation-specific aggregation + within-partition cross-relation combines
    are shard-local tensor ops (``segment_sum`` over the *local* branch axis);
  * the only model-axis collective is one ``psum`` of the root partials
    [batch, hidden] per step — Θ(|B|·hidden), the paper's Prop-2 bound —
    plus the loss scalar;
  * the batch axis is sharded over (``"pod"``, ``"data"``) — the paper's
    intra-machine data parallelism.

The stacking layer is **scope-driven** (relation-module IR, DESIGN.md §3):
for every parameter scope the model declares — per-(relation, layer),
per-(node-type, layer), per-(edge-type, layer) — the plan carries per-shard
unique storage-key lists, per-slot index arrays, and shared-slot groups.
``stack_params_from_dict`` packs each scope's parameters into ``[P, U, ...]``
slabs, the per-level aggregation gathers per-slot leaves and ``vmap``s the
module's *own* ``aggregate`` over the branch axis, and
:func:`sync_stack_grads` all-reduces gradients of parameters that appear in
more than one slot (HGT's per-node-type K/Q/V being the canonical case) so
shard-local copies follow the exact dict-mode optimizer trajectory.  All
registered models run here — there is no per-model branching.

A ``local_combine=False`` mode emulates *naive* relation placement (branches
scattered without metatree awareness): inner-level partial aggregations must
then cross the model axis as full [R, N, hidden] psums — the paper's 8.0 MB
case, used as the ablation baseline in benchmarks and §Perf.

Everything is static-shaped: branch counts are padded per shard, dummy slots
carry zeroed parameters and all-False masks.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs
from repro.core.hgnn import HGNNConfig, Params, rel_context
from repro.core.raf import BranchAssignment
from repro.core.relmod import SCOPE_CONTAINER, storage_key
from repro.data.staging import StackRecipe, stack_batch_host
from repro.graph.sampler import SampledBatch, SampleSpec

__all__ = [
    "StackedPlan",
    "build_plan",
    "stack_params_from_dict",
    "stack_batch",
    "stack_recipe",
    "gather_resident",
    "gather_leaf",
    "feat_grads_as_staged",
    "raf_spmd_forward",
    "sync_stack_grads",
    "make_loss_fn",
    "make_train_step",
    "make_grad_step",
    "make_apply_step",
]


# --------------------------------------------------------------------------
# static plan
# --------------------------------------------------------------------------


@dataclasses.dataclass
class LevelPlan:
    depth: int
    layer: int
    fanout: int
    d_in: int  # aggregation input dim (d_pad at the leaf layer, hidden above)
    slot_branch: np.ndarray  # [P, rb] original branch index, -1 for dummies
    parent_local: np.ndarray  # [P, rb] parent slot within the shard, level d-1
    parent_global: np.ndarray  # [P, rb] parent global slot (naive mode)
    # per scope the model declares: [P, rb] index into that scope's layer stack
    slot_u: Dict[str, np.ndarray]
    valid: np.ndarray  # [P, rb] bool

    @property
    def rb(self) -> int:
        return self.slot_branch.shape[1]


@dataclasses.dataclass
class StackedPlan:
    spec: SampleSpec
    cfg: HGNNConfig
    num_shards: int
    d_pad: int
    levels: List[LevelPlan]
    # (scope, layer) -> per-shard list of storage keys occupying stack slots
    scope_keys: Dict[Tuple[str, int], List[List[str]]]
    # (scope, layer) -> [P, U] global group id per slot (shared-param sync);
    # slots holding the same storage key share an id, unused slots get
    # singleton ids, so segment-summing gradients over groups is exact
    slot_groups: Dict[Tuple[str, int], np.ndarray]
    src_types: List[List[str]]  # per level: src type per original branch
    dst_types: List[List[str]]  # per level: dst type per original branch

    @property
    def module(self):
        return self.cfg.module

    @property
    def layers(self) -> List[int]:
        return sorted({layer for (_, layer) in self.scope_keys})

    def u_of(self, scope: str, layer: int) -> int:
        return max(1, max(len(row) for row in self.scope_keys[(scope, layer)]))

    def has_shared(self, scope: str, layer: int) -> bool:
        """Whether any storage key occupies more than one stack slot (then
        gradients need cross-slot summing to match the dict-mode trajectory)."""
        rows = self.scope_keys[(scope, layer)]
        keys = [nm for row in rows for nm in row]
        return len(keys) != len(set(keys))

    def layer_shape_ctx(self, layer: int):
        d_in = self.d_pad if layer == 1 else self.cfg.hidden
        return self.cfg.shape_ctx(d_src=d_in, d_dst=self.d_pad)


def build_plan(
    spec: SampleSpec,
    assignment: BranchAssignment,
    cfg: HGNNConfig,
    feat_dims: Dict[str, int],
) -> StackedPlan:
    module = cfg.module
    Pn = assignment.num_partitions
    k = spec.num_layers
    dims = lambda t: feat_dims.get(t, cfg.learnable_dim)
    all_types = set([spec.target_type])
    for lv in spec.levels:
        for b in lv:
            all_types.add(b.rel.src)
    d_pad = max(dims(t) for t in all_types)

    # paper-faithful bookkeeping of src/dst types per branch (feature gathers)
    src_types, dst_types = [], []
    parents = [spec.target_type]
    for lv in spec.levels:
        src_types.append([b.rel.src for b in lv])
        dst_types.append([parents[b.parent] for b in lv])
        parents = [b.rel.src for b in lv]

    # group branches by owner, pad to uniform per-shard counts
    slot_of: List[Dict[int, Tuple[int, int]]] = []  # per level: branch -> (p, slot)
    level_plans: List[LevelPlan] = []
    scope_keys: Dict[Tuple[str, int], List[List[str]]] = {}
    for d in range(1, k + 1):
        layer = k - d + 1
        owners = assignment.owner[d - 1]
        by_p: List[List[int]] = [[] for _ in range(Pn)]
        for b, o in enumerate(owners):
            by_p[int(o)].append(b)
        rb = max(1, max(len(x) for x in by_p))
        slot_branch = np.full((Pn, rb), -1, dtype=np.int64)
        valid = np.zeros((Pn, rb), dtype=bool)
        smap: Dict[int, Tuple[int, int]] = {}
        for p in range(Pn):
            for s, b in enumerate(by_p[p]):
                slot_branch[p, s] = b
                valid[p, s] = True
                smap[b] = (p, s)
        slot_of.append(smap)

        # per-scope, per-shard unique storage-key lists + per-slot indices
        slot_u: Dict[str, np.ndarray] = {}
        for scope in module.scopes:
            names = scope_keys.setdefault((scope, layer), [[] for _ in range(Pn)])
            u_arr = np.zeros((Pn, rb), dtype=np.int64)
            for p in range(Pn):
                for s, b in enumerate(by_p[p]):
                    bs = spec.levels[d - 1][b]
                    ctx = rel_context(bs.rel, dst_types[d - 1][b], layer)
                    nm = storage_key(scope, ctx)
                    if nm not in names[p]:
                        names[p].append(nm)
                    u_arr[p, s] = names[p].index(nm)
            slot_u[scope] = u_arr

        # parent mapping
        parent_local = np.zeros((Pn, rb), dtype=np.int64)
        parent_global = np.zeros((Pn, rb), dtype=np.int64)
        if d > 1:
            prev = level_plans[-1]
            for p in range(Pn):
                for s in range(rb):
                    b = slot_branch[p, s]
                    if b < 0:
                        continue
                    pb = spec.levels[d - 1][b].parent
                    pp, ps = slot_of[d - 2][pb]
                    parent_global[p, s] = pp * prev.rb + ps
                    parent_local[p, s] = ps
                    if pp != p and assignment.meta_local:
                        raise AssertionError("meta-local assignment violated")
        level_plans.append(
            LevelPlan(
                depth=d,
                layer=layer,
                fanout=spec.fanouts[d - 1],
                d_in=d_pad if d == k else cfg.hidden,
                slot_branch=slot_branch,
                parent_local=parent_local,
                parent_global=parent_global,
                slot_u=slot_u,
                valid=valid,
            )
        )

    # shared-slot groups: same storage key (any shard, any slot) -> same id;
    # unused padding slots get fresh singleton ids
    slot_groups: Dict[Tuple[str, int], np.ndarray] = {}
    for (scope, layer), names in scope_keys.items():
        U = max(1, max(len(row) for row in names))
        uniq = sorted({nm for row in names for nm in row})
        gid = {nm: i for i, nm in enumerate(uniq)}
        groups = np.zeros((Pn, U), dtype=np.int64)
        nxt = len(uniq)
        for p in range(Pn):
            for u in range(U):
                if u < len(names[p]):
                    groups[p, u] = gid[names[p][u]]
                else:
                    groups[p, u] = nxt
                    nxt += 1
        slot_groups[(scope, layer)] = groups

    return StackedPlan(
        spec=spec,
        cfg=cfg,
        num_shards=Pn,
        d_pad=d_pad,
        levels=level_plans,
        scope_keys=scope_keys,
        slot_groups=slot_groups,
        src_types=src_types,
        dst_types=dst_types,
    )


# --------------------------------------------------------------------------
# parameter stacking
# --------------------------------------------------------------------------


def stack_params_from_dict(plan: StackedPlan, params: Params) -> Dict:
    """Pack dict-form parameters (``init_hgnn_params``) into per-layer stacks
    ``{f"layer{l}": {leaf: [P, U_scope, ...]}}`` with input dims padded to
    the plan's common widths (``d_pad`` for feature-facing axes).  Padding
    regions are zero, so padded feature slots contribute nothing and the
    stacked forward is bit-equivalent to the dict forward."""
    module = plan.module
    stacks: Dict = {}
    for layer in plan.layers:
        sc = plan.layer_shape_ctx(layer)
        entry = {}
        for spec_ in module.specs:
            names = plan.scope_keys[(spec_.scope, layer)]
            U = plan.u_of(spec_.scope, layer)
            padded = tuple(spec_.shape(sc))
            arr = np.zeros((plan.num_shards, U) + padded, np.float32)
            container = params[SCOPE_CONTAINER[spec_.scope]]
            for p, row in enumerate(names):
                for u, nm in enumerate(row):
                    w = np.asarray(container[nm][spec_.name])
                    arr[(p, u) + tuple(slice(0, s) for s in w.shape)] = w
            entry[spec_.name] = jnp.asarray(arr)
        stacks[f"layer{layer}"] = entry
    # copy (not alias) the head: the train step donates its inputs, and an
    # aliased caller-owned array would be deleted out from under the caller
    stacks["head"] = jax.tree.map(lambda a: jnp.array(a, copy=True), params["head"])
    return stacks


# --------------------------------------------------------------------------
# batch stacking (host-side feature gathers)
# --------------------------------------------------------------------------


def stack_recipe(plan: StackedPlan) -> StackRecipe:
    """The plan's picklable host-staging recipe (memoized on the plan) —
    what a jax-free sampler worker needs to run :func:`stack_batch_host`
    (see ``repro.data.staging`` and DESIGN.md §9)."""
    recipe = getattr(plan, "_stack_recipe", None)
    if recipe is None:
        recipe = StackRecipe.from_plan(plan)
        plan._stack_recipe = recipe
    return recipe


def stack_batch(
    plan: StackedPlan,
    batch: SampledBatch,
    tables: Dict[str, np.ndarray],
) -> Dict:
    """Assemble the stacked device arrays for one sampled batch.

    ``tables`` must contain a feature table for every node type (learnable
    tables included — the embed engine supplies them).  Feature gathers for a
    shard's branches touch only node types present in its partition, matching
    Heta's locality argument; we materialize all shards' slices because the
    test/driver processes run every shard on one host.

    The host-side gather work is the shared numpy core
    :func:`repro.data.staging.stack_batch_host` — the multi-worker sampling
    pool runs the same function inside worker processes, so worker-staged
    and consumer-staged batches are bit-identical by construction.  Putting
    its arrays on the device is the span ``heta.stage.put`` (counter
    ``heta.stage.put_bytes``: their bytes).

    This is the host path.  Where every table is wholly in the device cache
    on a one-accelerator mesh, the training executor stages node ids instead
    (:func:`repro.data.staging.stack_batch_nids`) and the compiled step
    gathers the rows (:func:`gather_resident`); full-graph inference keeps
    this path.
    """
    host = stack_batch_host(stack_recipe(plan), batch, tables)
    with obs.span("heta.stage.put"):
        obs.count("heta.stage.put_bytes", sum(v.nbytes for v in host.values()))
        return {k: jnp.asarray(v) for k, v in host.items()}


def gather_resident(plan: StackedPlan, arrays: Dict, tables=None,
                    kernels=None) -> Dict:
    """The device half of resident staging (traceable; inside the compiled
    step).  ``arrays`` carry the int32 node ids ``qnid{d}``/``hnid{k}`` of
    ``repro.data.staging.stack_batch_nids``; ``tables`` maps each node type
    to its cache's device rows, which a cache that holds every row keeps in
    node-id order.

    XLA's gather, one ``take`` per branch slot from the slot's static
    source type, widths below ``d_pad`` zero-padded and padding slots zero.
    ``qfeat{d}`` is the array ``stack_batch_host`` builds on the host; the
    leaf level's ``hfeat{k}`` is taken straight in the layout its
    aggregation reads (:func:`gather_leaf`), with ``kernels`` deciding the
    padded fanout.  With ``tables=None`` the arrays already hold the
    features and pass through unchanged."""
    from repro.kernels.stacked_relation_agg import fanout_tile

    if tables is None:
        return arrays
    out = dict(arrays)
    k = plan.spec.num_layers
    for d in range(1, k + 1):
        sb = plan.levels[d - 1].slot_branch.reshape(-1)
        slots = [tables[plan.dst_types[d - 1][b]] if b >= 0 else None
                 for b in sb]
        out[f"qfeat{d}"] = _take_rows(slots, out.pop(f"qnid{d}"), plan.d_pad)
    lp = plan.levels[k - 1]
    tile = fanout_tile(plan.module, kernels)
    out[f"hfeat{k}"] = gather_leaf(
        [tables[plan.src_types[k - 1][b]] if b >= 0 else None
         for b in lp.slot_branch.reshape(-1)],
        out.pop(f"hnid{k}"), out[f"mask{k}"], fanout=lp.fanout,
        f_k=-(-lp.fanout // tile) * tile, d_pad=plan.d_pad)
    return out


def _take_rows(slots: Sequence, ids, d_pad: int, keep=None):
    """``stack`` over slots of ``take(slots[s], ids[s])`` as float32, its
    lanes zero-padded to ``d_pad``; zero where ``keep[s]`` is false and on a
    padding slot (``None``)."""
    rows = []
    for s, (tab, i) in enumerate(zip(slots, ids)):
        if tab is None:
            rows.append(jnp.zeros(i.shape + (d_pad,), jnp.float32))
            continue
        r = jnp.take(tab, i, axis=0, mode="clip").astype(jnp.float32)
        if keep is not None:
            r = jnp.where(keep[s][..., None], r, 0.0)
        rows.append(jnp.pad(r, [(0, 0)] * i.ndim + [(0, d_pad - tab.shape[1])]))
    return jnp.stack(rows)


def gather_leaf(slots: Sequence, nids, mask, *, fanout: int, f_k: int,
                d_pad: int):
    """The leaf level's rows as its aggregation reads them,
    ``[P*rb, n_prev, f_k, d_pad]`` float32: ``nids`` and ``mask``
    (``[P*rb, n_prev * fanout]``, as staged) are laid out ``[n_prev,
    f_k]`` before the gather, so XLA takes each slot's rows straight into
    that layout, with no relayout of fanout ``fanout`` into whole sublane
    tiles and no padded copy to ``f_k``.  ``out[s, i, j]`` is row
    ``nids[s, i*fanout + j]`` of ``slots[s]`` where ``mask`` keeps it, zero
    on the rest, on the fanout padding ``j >= fanout`` and on a padding slot
    (``None``): the slots the aggregation weights zero."""
    S = nids.shape[0]
    pad = ((0, 0), (0, 0), (0, f_k - fanout))
    ids = jnp.pad(nids.reshape(S, -1, fanout), pad)
    keep = jnp.pad(mask.reshape(S, -1, fanout), pad)
    return _take_rows(slots, ids, d_pad, keep)


def feat_grads_as_staged(plan: StackedPlan, gf: Dict) -> Dict:
    """Gradients of the gathered feature arrays in the layout staging gives
    them: a leaf ``hfeat{k}`` gathered 4-D (:func:`gather_resident`) has
    its fanout padding cut and its gradient back to ``[P*rb, n_d, d_pad]``,
    which is what ``executors.apply_feature_grads`` reads."""
    k = plan.spec.num_layers
    g = gf.get(f"hfeat{k}")
    if g is None or g.ndim == 3:
        return gf
    f = plan.levels[k - 1].fanout
    return {**gf, f"hfeat{k}": g[:, :, :f].reshape(g.shape[0], -1, g.shape[3])}


# --------------------------------------------------------------------------
# the sharded forward
# --------------------------------------------------------------------------


def _agg_level(plan: StackedPlan, lp: LevelPlan, stacks, h_in, qfeat, mask,
               shard_idx, kernels=None):
    """Relation-specific aggregation for one level on one shard.

    Dispatches through :func:`repro.kernels.stacked_relation_agg.stacked_agg`
    (DESIGN.md §8): on the fused path one Pallas call covers every branch
    slot, reading each slot's weight block straight from the ``[U, ...]``
    stack via scalar-prefetched scope indices; otherwise the historical
    oracle gathers per-slot leaves and ``vmap``s the module's ``aggregate``.
    The per-shard slot indices are *traced* (``shard_idx`` differs per
    shard), which is exactly what the scalar-prefetch indirection supports.

    h_in  [rb, n_d, d_in] or [rb, n_prev, f_k, d_in] -> out [rb, n_prev, hidden]
    """
    from repro.kernels.stacked_relation_agg import stacked_agg

    module = plan.module
    layer = stacks[f"layer{lp.layer}"]
    valid = jnp.asarray(lp.valid)[shard_idx]  # [rb]
    local = {s.name: layer[s.name][0] for s in module.specs}  # each [U, ...]
    slot_u = {
        scope: jnp.asarray(lp.slot_u[scope])[shard_idx] for scope in module.scopes
    }  # each [rb]
    f = lp.fanout
    if h_in.ndim == 3:  # [rb, n_d, d_in] as staged, or a level's activations
        rb, n_d, d_in = h_in.shape
        hg = h_in.reshape(rb, n_d // f, f, d_in)
    else:  # gathered on the chip at the padded fanout (gather_resident)
        hg = h_in
    rb, n_prev, f_k = hg.shape[:3]
    mg = mask.reshape(rb, n_prev, f)
    if f_k > f:
        mg = jnp.pad(mg, ((0, 0), (0, 0), (0, f_k - f)))
    out = stacked_agg(module, local, slot_u, hg, qfeat, mg, opts=kernels)
    return out * valid[:, None, None].astype(out.dtype)


def raf_spmd_forward(
    plan: StackedPlan,
    stacks: Dict,
    arrays: Dict,
    model_axis: str = "model",
    local_combine: bool = True,
    kernels=None,
):
    """Per-shard body (runs inside shard_map).  Returns root embedding
    [B_local, hidden] (replicated over the model axis after the psum).

    ``kernels`` (a ``KernelConfig``/``KernelOptions``-shaped object or
    ``None``) selects the aggregation backend per level — the fused stacked
    Pallas kernels by default on TPU, the vmap oracle elsewhere."""
    k = plan.spec.num_layers
    shard_idx = jax.lax.axis_index(model_axis)
    child: Optional[jnp.ndarray] = None
    for d in range(k, 0, -1):
        lp = plan.levels[d - 1]
        if d == k:
            h_in = arrays[f"hfeat{d}"]
        else:
            h_in = jax.nn.relu(child)
        out = _agg_level(
            plan, lp, stacks, h_in, arrays[f"qfeat{d}"], arrays[f"mask{d}"],
            shard_idx, kernels,
        )
        if d == 1:
            partial = jnp.sum(out, axis=0)  # shard's partial aggregation [B, H]
            root = jax.lax.psum(partial, model_axis)  # RAF exchange (Alg.1 l.6)
        else:
            prev_rb = plan.levels[d - 2].rb
            if local_combine:
                seg = jnp.asarray(lp.parent_local)[shard_idx]
                child = jax.ops.segment_sum(out, seg, num_segments=prev_rb)
            else:
                # naive placement: parents may be remote -> full inner-level
                # exchange of [R_{d-1}, N, H] partials (the ablation case)
                seg = jnp.asarray(lp.parent_global)[shard_idx]
                full = jax.ops.segment_sum(
                    out, seg, num_segments=prev_rb * plan.num_shards
                )
                full = jax.lax.psum(full, model_axis)
                child = jax.lax.dynamic_slice_in_dim(
                    full, shard_idx * prev_rb, prev_rb, axis=0
                )
    return root


# --------------------------------------------------------------------------
# shared-parameter gradient synchronization
# --------------------------------------------------------------------------


def sync_stack_grads(plan: StackedPlan, grads: Dict) -> Dict:
    """Sum gradients across stack slots holding the *same* parameter and
    broadcast the sum back to every copy.

    A storage key can occupy several slots — a node type feeding relations
    owned by different shards (HGT's K/Q/V), or one relation sampled into
    branches assigned to different partitions.  ``stack_params_from_dict``
    seeds all copies identically; summed (hence identical) gradients keep
    the per-copy Adam trajectories identical too, so the stacked run follows
    the dict-form run exactly — Prop 1 extends through training.  Under
    GSPMD the segment-sum over the ``[P·U]`` group axis lowers to the
    cross-shard collective this semantically is; scopes with no sharing are
    left untouched (no collective emitted).
    """
    scope_of = {s.name: s.scope for s in plan.module.specs}
    out = dict(grads)
    for layer in plan.layers:
        entry = dict(grads[f"layer{layer}"])
        for leaf, g in entry.items():
            scope = scope_of[leaf]
            if not plan.has_shared(scope, layer):
                continue
            groups = plan.slot_groups[(scope, layer)]
            seg = jnp.asarray(groups.reshape(-1))
            nseg = int(groups.max()) + 1
            flat = g.reshape((groups.size,) + g.shape[2:])
            summed = jax.ops.segment_sum(flat, seg, num_segments=nseg)
            entry[leaf] = summed[seg].reshape(g.shape)
        out[f"layer{layer}"] = entry
    return out


# --------------------------------------------------------------------------
# jitted train step
# --------------------------------------------------------------------------


def _array_specs(plan: StackedPlan, data_axes, model_axis, leaf_ndim: int = 3):
    k = plan.spec.num_layers
    specs = {"seeds": P(data_axes), "labels": P(data_axes)}
    for d in range(1, k + 1):
        specs[f"mask{d}"] = P(model_axis, data_axes)
        specs[f"qfeat{d}"] = P(model_axis, data_axes, None)
        specs[f"qnid{d}"] = P(model_axis, data_axes)
        if d == k:
            # [P*rb, n_d, d] as staged; [P*rb, n_prev, f_k, d] as the
            # resident gather lays it out
            specs[f"hfeat{d}"] = P(model_axis, data_axes, None,
                                   *([None] * (leaf_ndim - 3)))
            specs[f"hnid{d}"] = P(model_axis, data_axes)
    return specs


def _stack_specs(plan: StackedPlan):
    """Sharding specs for the parameter stacks: every leaf is sharded along
    the leading (shard) axis, replicated elsewhere — derived from the
    module's declared shapes, no per-model cases."""
    specs = {}
    for layer in plan.layers:
        sc = plan.layer_shape_ctx(layer)
        specs[f"layer{layer}"] = {
            s.name: P("model", *([None] * (1 + len(s.shape(sc)))))
            for s in plan.module.specs
        }
    specs["head"] = {"w": P(None, None), "b": P(None)}
    return specs


def _build_loss_fn(
    plan: StackedPlan,
    mesh: Mesh,
    model_axis: str,
    data_axes: Tuple[str, ...],
    local_combine: bool,
    kernels=None,
):
    """Shared closure of the train and eval steps: ``(loss_fn, split_arrays)``
    where ``loss_fn(stacks, feats, rest)`` is the scalar SPMD loss."""
    da = data_axes if isinstance(data_axes, tuple) else (data_axes,)
    arr_specs = _array_specs(plan, da, model_axis)
    stack_specs = _stack_specs(plan)
    rel_specs = {k2: v for k2, v in stack_specs.items() if k2 != "head"}

    def split_arrays(arrays):
        feats = {k2: v for k2, v in arrays.items() if "feat" in k2}
        rest = {k2: v for k2, v in arrays.items() if "feat" not in k2}
        return feats, rest

    def root_fn(rel_stacks, feats, rest):
        def body(stacks_s, feats_s, rest_s):
            return raf_spmd_forward(
                plan, stacks_s, {**feats_s, **rest_s}, model_axis, local_combine,
                kernels,
            )

        leaf = feats.get(f"hfeat{plan.spec.num_layers}")
        feat_specs = _array_specs(plan, da, model_axis,
                                  3 if leaf is None else leaf.ndim)
        return jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(
                rel_specs,
                {k2: feat_specs[k2] for k2 in feats},
                {k2: arr_specs[k2] for k2 in rest},
            ),
            out_specs=P(da, None),
            check_vma=False,
        )(rel_stacks, feats, rest)

    def loss_fn(stacks, feats, rest):
        rel_stacks = {k2: v for k2, v in stacks.items() if k2 != "head"}
        root = root_fn(rel_stacks, feats, rest)
        h = jax.nn.relu(root)
        logits = h @ stacks["head"]["w"] + stacks["head"]["b"]
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, rest["labels"][:, None], axis=-1)
        return jnp.mean(nll)

    return loss_fn, split_arrays


def make_loss_fn(
    plan: StackedPlan,
    mesh: Mesh,
    model_axis: str = "model",
    data_axes=("data",),
    local_combine: bool = True,
    kernels=None,
):
    """Jitted evaluation-only loss: ``loss(stacks, arrays[, tables]) ->
    scalar``; with ``tables`` the arrays are resident staging's node ids
    (:func:`gather_resident`)."""
    loss_fn, split_arrays = _build_loss_fn(plan, mesh, model_axis, data_axes,
                                           local_combine, kernels)

    @jax.jit
    def eval_loss(stacks, arrays, tables=None):
        feats, rest = split_arrays(
            gather_resident(plan, arrays, tables, kernels))
        return loss_fn(stacks, feats, rest)

    return eval_loss


def make_train_step(
    plan: StackedPlan,
    mesh: Mesh,
    adam_cfg,
    model_axis: str = "model",
    data_axes=("data",),
    local_combine: bool = True,
    learn_feats: bool = False,
    kernels=None,
):
    """Build the jitted SPMD RAF train step.

    ``step(stacks, opt_state, arrays[, tables]) -> (stacks, opt_state, loss[, feat_grads])``

    ``arrays`` hold the staged features (``stack_batch``), or with
    ``tables`` — each node type's device cache rows, a jit argument,
    neither baked in nor donated — resident staging's node ids,
    which the step gathers into the same feature arrays first
    (:func:`gather_resident`, outside the differentiated function).

    The shard_map body computes the root embedding (ending in the RAF psum);
    the classifier head + loss run outside under GSPMD, so gradients of the
    replicated head are exact.  Stack gradients pass through
    :func:`sync_stack_grads` before Adam, so parameters shared across shard
    slots stay consistent copies (the fused kernels' custom VJP already
    accumulates slot gradients into each shard's ``[U, ...]`` rows —
    cross-shard sharing remains this sync's job).  With ``learn_feats=True``
    the step also returns gradients w.r.t. the gathered feature arrays
    (``qfeat*``/``hfeat*``) for the embed engine's sparse row updates.
    """
    from repro.optim.adam import adam_update

    loss_fn, split_arrays = _build_loss_fn(plan, mesh, model_axis, data_axes,
                                           local_combine, kernels)

    if not learn_feats:
        grad_fn = jax.value_and_grad(loss_fn)

        @partial(jax.jit, donate_argnums=(0, 1))
        def step(stacks, opt_state, arrays, tables=None):
            feats, rest = split_arrays(
                gather_resident(plan, arrays, tables, kernels))
            loss, grads = grad_fn(stacks, feats, rest)
            grads = sync_stack_grads(plan, grads)
            stacks, opt_state = adam_update(adam_cfg, stacks, grads, opt_state)
            return stacks, opt_state, loss

        return step

    grad_fn2 = jax.value_and_grad(loss_fn, argnums=(0, 1))

    @partial(jax.jit, donate_argnums=(0, 1))
    def step_feats(stacks, opt_state, arrays, tables=None):
        feats, rest = split_arrays(
            gather_resident(plan, arrays, tables, kernels))
        loss, (gs, gf) = grad_fn2(stacks, feats, rest)
        gs = sync_stack_grads(plan, gs)
        stacks, opt_state = adam_update(adam_cfg, stacks, gs, opt_state)
        return stacks, opt_state, loss, feat_grads_as_staged(plan, gf)

    return step_feats


def make_grad_step(
    plan: StackedPlan,
    mesh: Mesh,
    model_axis: str = "model",
    data_axes=("data",),
    local_combine: bool = True,
    kernels=None,
):
    """Jitted forward/backward half of :func:`make_train_step` for the
    multi-process data-parallel tier (``repro.data.dp_trainer``, DESIGN.md
    §13): ``grad(stacks, arrays) -> (loss, grads)`` with *raw* stack
    gradients.  The DP trainer allreduces these across trainer processes in
    fixed rank order and only then runs :func:`make_apply_step` — which
    performs :func:`sync_stack_grads` + Adam — so the cross-slot sync
    happens exactly once, on the cross-trainer sum, preserving the
    single-process sync discipline."""
    loss_fn, split_arrays = _build_loss_fn(plan, mesh, model_axis, data_axes,
                                           local_combine, kernels)
    grad_fn = jax.value_and_grad(loss_fn)

    @jax.jit
    def grad(stacks, arrays):
        feats, rest = split_arrays(arrays)
        return grad_fn(stacks, feats, rest)

    return grad


def make_apply_step(plan: StackedPlan, adam_cfg):
    """Jitted update half of :func:`make_train_step` (see
    :func:`make_grad_step`): ``apply(stacks, opt_state, grads) ->
    (stacks, opt_state)`` — :func:`sync_stack_grads` on the (already
    cross-trainer-summed) gradients, then Adam."""
    from repro.optim.adam import adam_update

    @partial(jax.jit, donate_argnums=(0, 1))
    def apply_grads(stacks, opt_state, grads):
        grads = sync_stack_grads(plan, grads)
        return adam_update(adam_cfg, stacks, grads, opt_state)

    return apply_grads


def shard_arrays(plan: StackedPlan, mesh: Mesh, arrays: Dict, data_axes=("data",),
                 model_axis: str = "model") -> Dict:
    """Device-put stacked batch arrays with their production shardings
    (span ``heta.stage.put``; counter ``heta.stage.put_bytes``: the bytes of
    the host arrays among them, which travel host -> device)."""
    da = data_axes if isinstance(data_axes, tuple) else (data_axes,)
    specs = _array_specs(plan, da, model_axis)
    with obs.span("heta.stage.put"):
        obs.count("heta.stage.put_bytes", sum(
            v.nbytes for v in arrays.values() if isinstance(v, np.ndarray)))
        return {
            k2: jax.device_put(v, NamedSharding(mesh, specs[k2]))
            for k2, v in arrays.items()
        }


def shard_stacks(plan: StackedPlan, mesh: Mesh, stacks: Dict) -> Dict:
    specs = _stack_specs(plan)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        stacks,
        specs,
        is_leaf=lambda x: isinstance(x, jnp.ndarray),
    )
