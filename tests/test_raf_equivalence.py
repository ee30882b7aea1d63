"""Prop 1 (mathematical equivalence): RAF == vanilla, bit-for-bit.

Covers the simulated executor AND the SPMD stacked executor for all three
HGNN models (the relation-module IR drives both), across partition counts
and datasets — forward logits and parameter gradients.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hgnn import (
    HGNNConfig,
    batch_to_arrays,
    hgnn_forward,
    hgnn_loss,
    init_embed_tables,
    init_hgnn_params,
)
from repro.core.meta_partition import meta_partition
from repro.core.raf import (
    assign_branches,
    raf_comm_bytes,
    raf_forward,
    random_branch_assignment,
)
from repro.graph.sampler import NeighborSampler, SampleSpec
from repro.graph.synthetic import donor_like, ogbn_mag_like
from repro.launch.mesh import make_mesh


def _setup(graph, model, num_parts, fanouts=(4, 3), batch=16):
    mp = meta_partition(graph, num_parts, num_layers=len(fanouts))
    spec = SampleSpec.from_metatree(mp.metatree, fanouts)
    sampler = NeighborSampler(graph, spec, batch, seed=1)
    b = sampler.sample_batch(graph.train_nodes[:batch])
    cfg = HGNNConfig(model=model, hidden=32, num_layers=len(fanouts),
                     num_classes=graph.num_classes)
    feat_dims = {t: graph.feat_dim(t) for t in graph.num_nodes if graph.feat_dim(t)}
    key = jax.random.PRNGKey(0)
    params = init_hgnn_params(key, cfg, spec, feat_dims)
    params["embed"] = init_embed_tables(
        jax.random.PRNGKey(1), cfg, graph.num_nodes, feat_dims
    )
    tables = {t: jnp.asarray(f) for t, f in graph.features.items()}
    return mp, spec, b, cfg, feat_dims, key, params, tables


@pytest.mark.parametrize("model", ["rgcn", "rgat", "hgt"])
@pytest.mark.parametrize("num_parts", [2, 3])
def test_prop1_simulated(model, num_parts):
    g = ogbn_mag_like(scale=0.002)
    mp, spec, b, cfg, feat_dims, key, params, tables = _setup(g, model, num_parts)
    arrs = batch_to_arrays(b)
    ref = hgnn_forward(cfg, params, tables, arrs, spec)

    assignment = assign_branches(spec, mp)
    assert assignment.meta_local
    parts = []
    for p in range(num_parts):
        rels = assignment.relations_of(p, spec)
        pp = init_hgnn_params(key, cfg, spec, feat_dims, restrict_rels=rels)
        pp["embed"] = params["embed"]
        pp["head"] = params["head"]
        parts.append(pp)
    out = raf_forward(cfg, parts, tables, arrs, spec, assignment)
    # Prop 1 holds exactly in real arithmetic; fp32 reassociation of the
    # cross-partition sum gives O(1e-8) differences
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


def test_prop1_featureless_and_varying_dims():
    """Donor-like: wildly varying feature dims (7..789) must not break
    equivalence (the padding path)."""
    g = donor_like(scale=0.001)
    mp, spec, b, cfg, feat_dims, key, params, tables = _setup(g, "rgcn", 2)
    arrs = batch_to_arrays(b)
    ref = hgnn_forward(cfg, params, tables, arrs, spec)
    assignment = assign_branches(spec, mp)
    parts = []
    for p in range(2):
        rels = assignment.relations_of(p, spec)
        pp = init_hgnn_params(key, cfg, spec, feat_dims, restrict_rels=rels)
        pp["embed"], pp["head"] = params["embed"], params["head"]
        parts.append(pp)
    out = raf_forward(cfg, parts, tables, arrs, spec, assignment)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


@pytest.mark.parametrize("kernels_on", [False, True], ids=["kernels_off", "kernels_on"])
@pytest.mark.parametrize("model", ["rgcn", "rgat", "hgt"])
def test_prop1_spmd_stacked(model, kernels_on):
    """The stacked/padded SPMD representation is bit-equivalent to the dict
    forward for every registered model — including HGT's per-node-type
    parameter structure (single-device mesh; the multi-device case runs in
    test_multidevice.py via subprocess).  Parametrized over the kernel
    layer: ``kernels_on`` forces the fused Pallas path in interpret mode."""
    from repro.core import raf_spmd
    from repro.kernels.ops import KernelOptions

    kernels = KernelOptions(interpret=True) if kernels_on else KernelOptions(enabled=False)
    g = ogbn_mag_like(scale=0.002)
    mp, spec, b, cfg, feat_dims, key, params, tables = _setup(g, model, 2)
    arrs = batch_to_arrays(b)
    ref = hgnn_forward(cfg, params, tables, arrs, spec)

    # single real device: fold both partitions onto one model shard (the
    # multi-device path runs in test_multidevice.py)
    assignment = assign_branches(spec, mp).fold(1, spec)
    plan = raf_spmd.build_plan(spec, assignment, cfg, feat_dims)
    stacks = raf_spmd.stack_params_from_dict(plan, params)
    tables_np = {t: np.asarray(f) for t, f in g.features.items()}
    tables_np.update({t: np.asarray(v) for t, v in params["embed"].items()})
    arrays = raf_spmd.stack_batch(plan, b, tables_np)

    mesh = make_mesh((1, 1), ("data", "model"))
    from jax.sharding import PartitionSpec as P

    arr_specs = raf_spmd._array_specs(plan, ("data",), "model")
    rel_specs = {k: v for k, v in raf_spmd._stack_specs(plan).items() if k != "head"}
    feats = {k: v for k, v in arrays.items() if "feat" in k}
    rest = {k: v for k, v in arrays.items() if "feat" not in k}

    def body(st, fe, re_):
        return raf_spmd.raf_spmd_forward(plan, st, {**fe, **re_}, "model", True,
                                         kernels)

    root = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(rel_specs, {k: arr_specs[k] for k in feats},
                  {k: arr_specs[k] for k in rest}),
        out_specs=P(("data",), None),
        check_vma=False,
    )({k: v for k, v in stacks.items() if k != "head"}, feats, rest)
    logits = jax.nn.relu(root) @ stacks["head"]["w"] + stacks["head"]["b"]
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("kernels_on", [False, True], ids=["kernels_off", "kernels_on"])
@pytest.mark.parametrize("model", ["rgcn", "rgat", "hgt"])
def test_prop1_spmd_gradients_match_vanilla(model, kernels_on):
    """Backprop through the stacked SPMD loss: gradients gathered back
    through the plan's scope index arrays equal the dict-form gradients
    (autodiff sums slot uses exactly like the dict forward sums relation
    occurrences).  With ``kernels_on`` the same holds through the fused
    Pallas kernels' custom VJPs (stack-form weight gradients)."""
    from repro.core import raf_spmd
    from repro.core.relmod import SCOPE_CONTAINER
    from repro.kernels.ops import KernelOptions

    kernels = KernelOptions(interpret=True) if kernels_on else KernelOptions(enabled=False)
    g = ogbn_mag_like(scale=0.002)
    mp, spec, b, cfg, feat_dims, key, params, tables = _setup(g, model, 2)
    arrs = batch_to_arrays(b)
    gref = jax.grad(lambda pr: hgnn_loss(cfg, pr, tables, arrs, spec))(params)

    assignment = assign_branches(spec, mp).fold(1, spec)
    plan = raf_spmd.build_plan(spec, assignment, cfg, feat_dims)
    stacks = raf_spmd.stack_params_from_dict(plan, params)
    tables_np = {t: np.asarray(f) for t, f in g.features.items()}
    tables_np.update({t: np.asarray(v) for t, v in params["embed"].items()})
    arrays = raf_spmd.stack_batch(plan, b, tables_np)

    mesh = make_mesh((1, 1), ("data", "model"))
    loss_fn, split = raf_spmd._build_loss_fn(plan, mesh, "model", ("data",), True,
                                             kernels)
    feats, rest = split(arrays)
    gstacks = jax.grad(loss_fn)(stacks, feats, rest)
    gstacks = raf_spmd.sync_stack_grads(plan, gstacks)  # single shard: identity

    for layer in plan.layers:
        for spec_ in plan.module.specs:
            names = plan.scope_keys[(spec_.scope, layer)]
            for p, row in enumerate(names):
                for u, nm in enumerate(row):
                    want = np.asarray(gref[SCOPE_CONTAINER[spec_.scope]][nm][spec_.name])
                    got = np.asarray(gstacks[f"layer{layer}"][spec_.name][p, u])
                    got = got[tuple(slice(0, s) for s in want.shape)]
                    np.testing.assert_allclose(
                        got, want, atol=1e-5,
                        err_msg=f"{model} grad mismatch {nm}/{spec_.name}",
                    )
    np.testing.assert_allclose(
        np.asarray(gstacks["head"]["w"]), np.asarray(gref["head"]["w"]), atol=1e-5
    )


def test_comm_bytes_meta_vs_naive():
    """§4 comm accounting: meta-local placement exchanges only root partials;
    naive placement adds inner-level traffic (the 0.5 MB vs 8 MB gap)."""
    g = ogbn_mag_like(scale=0.002)
    mp = meta_partition(g, 2, num_layers=2)
    spec = SampleSpec.from_metatree(mp.metatree, (25, 20))
    meta = assign_branches(spec, mp)
    naive = random_branch_assignment(spec, 2, seed=3)
    b_meta = raf_comm_bytes(spec, meta, 1024, 64)
    b_naive = raf_comm_bytes(spec, naive, 1024, 64)
    assert meta.meta_local and not naive.meta_local
    # meta: 2 × (P-1) × B × hidden × 2 bytes = 2·1·1024·64·2 = 0.26 MB
    assert b_meta == 2 * 1 * 1024 * 64 * 2
    assert b_naive > 10 * b_meta  # inner levels dominate (×fanout)


def test_gradients_match_vanilla():
    """Backprop equivalence: d(loss)/d(params) identical between executors
    for the shared head (Alg. 1 lines 12-17)."""
    g = ogbn_mag_like(scale=0.002)
    mp, spec, b, cfg, feat_dims, key, params, tables = _setup(g, "rgcn", 2)
    arrs = batch_to_arrays(b)

    gref = jax.grad(lambda pr: hgnn_loss(cfg, pr, tables, arrs, spec))(params)

    assignment = assign_branches(spec, mp)
    from repro.core.raf import raf_loss

    parts = []
    for p in range(2):
        rels = assignment.relations_of(p, spec)
        pp = init_hgnn_params(key, cfg, spec, feat_dims, restrict_rels=rels)
        pp["embed"], pp["head"] = params["embed"], params["head"]
        parts.append(pp)
    graf = jax.grad(
        lambda ps: raf_loss(cfg, ps, tables, arrs, spec, assignment)
    )(parts)
    # head grads must agree (partition 0 holds the designated head)
    np.testing.assert_allclose(
        np.asarray(graf[0]["head"]["w"]), np.asarray(gref["head"]["w"]), atol=1e-5
    )
    # per-relation grads: a (relation, layer) pair is *evaluated* by exactly
    # one partition (its sub-metatree owner), but restrict_rels keys by
    # relation name, so a partition may also hold never-evaluated copies at
    # other layers (zero grads).  Summing across partitions recovers the
    # vanilla gradient exactly.
    summed: dict = {}
    for p in range(2):
        for name, g_p in graf[p]["rel"].items():
            for leaf, val in g_p.items():
                if leaf.startswith("_"):
                    continue
                key2 = (name, leaf)
                summed[key2] = summed.get(key2, 0) + np.asarray(val)
    for (name, leaf), val in summed.items():
        np.testing.assert_allclose(
            val, np.asarray(gref["rel"][name][leaf]), atol=1e-5,
            err_msg=f"grad mismatch {name}/{leaf}",
        )
