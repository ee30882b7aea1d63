"""Relation-module IR (DESIGN.md §3): registry/config agreement, scope-driven
parameter stacking round-trips (property test), shared-slot gradient sync,
and new-model-as-pure-declaration extensibility."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.api.config import HGNN_MODELS, ModelConfig
from repro.core import raf_spmd, relmod
from repro.core.hgnn import (
    HGNNConfig,
    batch_to_arrays,
    hgnn_forward,
    init_embed_tables,
    init_hgnn_params,
)
from repro.core.meta_partition import meta_partition
from repro.core.raf import assign_branches
from repro.core.relmod import (
    SCOPE_CONTAINER,
    ParamSpec,
    RelationModule,
    available_models,
    get_relation_module,
    masked_mean,
    register_relation_module,
)
from repro.graph.sampler import NeighborSampler, SampleSpec
from repro.graph.synthetic import ogbn_mag_like
from repro.launch.mesh import make_mesh

_GRAPH = ogbn_mag_like(scale=0.002)


def _plan_and_params(model, num_parts, seed, fold=None):
    g = _GRAPH
    mp = meta_partition(g, num_parts, num_layers=2)
    spec = SampleSpec.from_metatree(mp.metatree, (4, 3))
    cfg = HGNNConfig(model=model, hidden=32, num_layers=2,
                     num_classes=g.num_classes)
    feat_dims = {t: g.feat_dim(t) for t in g.num_nodes if g.feat_dim(t)}
    params = init_hgnn_params(jax.random.PRNGKey(seed), cfg, spec, feat_dims)
    assignment = assign_branches(spec, mp)
    if fold is not None:
        assignment = assignment.fold(fold, spec)
    plan = raf_spmd.build_plan(spec, assignment, cfg, feat_dims)
    return plan, params


# --------------------------------------------------------------------------
# registry <-> config agreement
# --------------------------------------------------------------------------


def test_registry_is_the_source_of_truth():
    assert tuple(sorted(HGNN_MODELS)) == available_models()
    for name in HGNN_MODELS:
        assert get_relation_module(name).name == name
    with pytest.raises(KeyError, match="registered"):
        get_relation_module("gcn")
    with pytest.raises(ValueError, match="registered relation"):
        HGNNConfig(model="gcn")


def test_scopes_and_spec_validation():
    with pytest.raises(ValueError, match="scope"):
        ParamSpec("w", "per_galaxy", lambda c: (c.hidden,))
    with pytest.raises(ValueError, match="init"):
        ParamSpec("w", "relation", lambda c: (c.hidden,), init="ones")
    hgt = get_relation_module("hgt")
    assert set(hgt.scopes) == {"src_type", "dst_type", "etype"}
    assert get_relation_module("rgcn").scopes == ("relation",)


# --------------------------------------------------------------------------
# property: stacking round-trips bit-exactly (all models, varying partitions)
# --------------------------------------------------------------------------


@settings(max_examples=6, deadline=None)
@given(
    model=st.sampled_from(["rgcn", "rgat", "hgt"]),
    num_parts=st.integers(2, 3),
    seed=st.integers(0, 2**16),
)
def test_stack_round_trip_bit_exact(model, num_parts, seed):
    """``stack_params_from_dict`` followed by per-slot gather reproduces the
    dict params bit-for-bit, and every padding region is exactly zero."""
    plan, params = _plan_and_params(model, num_parts, seed)
    stacks = raf_spmd.stack_params_from_dict(plan, params)
    for layer in plan.layers:
        for spec_ in plan.module.specs:
            names = plan.scope_keys[(spec_.scope, layer)]
            stacked = np.asarray(stacks[f"layer{layer}"][spec_.name])
            seen = np.zeros(stacked.shape, bool)
            seen[:, len(max(names, key=len)):] = True  # fully-padded slots
            for p, row in enumerate(names):
                seen[p, len(row):] = True
                for u, nm in enumerate(row):
                    w = np.asarray(params[SCOPE_CONTAINER[spec_.scope]][nm][spec_.name])
                    sl = (p, u) + tuple(slice(0, s) for s in w.shape)
                    np.testing.assert_array_equal(stacked[sl], w)
                    seen[sl] = True
            # everything not covered by a real parameter is zero padding
            assert not stacked[~seen].any()


# --------------------------------------------------------------------------
# shared-slot gradient sync
# --------------------------------------------------------------------------


def test_sync_stack_grads_sums_shared_slots():
    """Slots holding the same storage key (hgt: a node type feeding relations
    on different shards) receive the cross-slot gradient sum; unshared and
    padding slots are untouched."""
    plan, params = _plan_and_params("hgt", 2, seed=0)
    shared = [(s, l) for (s, l) in plan.scope_keys if plan.has_shared(s, l)]
    assert shared, "ogbn-mag hgt plan must share node-type params across shards"

    stacks = raf_spmd.stack_params_from_dict(plan, params)
    # grads = distinct constant per slot, so sums are easy to predict
    grads = {}
    for key, entry in stacks.items():
        if key == "head":
            grads[key] = jax.tree.map(jnp.ones_like, entry)
            continue
        grads[key] = {
            leaf: (jnp.arange(g.shape[0] * g.shape[1], dtype=g.dtype)
                   .reshape(g.shape[0], g.shape[1], *([1] * (g.ndim - 2)))
                   * jnp.ones_like(g))
            for leaf, g in entry.items()
        }
    synced = raf_spmd.sync_stack_grads(plan, grads)
    scope_of = {s.name: s.scope for s in plan.module.specs}
    for layer in plan.layers:
        for leaf, g in grads[f"layer{layer}"].items():
            got = np.asarray(synced[f"layer{layer}"][leaf])
            names = plan.scope_keys[(scope_of[leaf], layer)]
            g = np.asarray(g)
            Pn, U = g.shape[:2]
            for p in range(Pn):
                for u in range(U):
                    if u >= len(names[p]):  # padding slot: identity
                        np.testing.assert_array_equal(got[p, u], g[p, u])
                        continue
                    total = sum(
                        g[p2, u2]
                        for p2, row in enumerate(names)
                        for u2, nm in enumerate(row)
                        if nm == names[p][u]
                    )
                    np.testing.assert_allclose(got[p, u], total, rtol=0, atol=0)
    # head gradients pass through untouched
    np.testing.assert_array_equal(
        np.asarray(synced["head"]["w"]), np.asarray(grads["head"]["w"])
    )


def test_restricted_init_matches_full_bit_exact():
    """Partition-restricted init (only a worker's relations) reproduces the
    full init's leaves exactly — name-derived keys, every model."""
    g = _GRAPH
    mp = meta_partition(g, 2, num_layers=2)
    spec = SampleSpec.from_metatree(mp.metatree, (4, 3))
    feat_dims = {t: g.feat_dim(t) for t in g.num_nodes if g.feat_dim(t)}
    assignment = assign_branches(spec, mp)
    for model in HGNN_MODELS:
        cfg = HGNNConfig(model=model, hidden=32, num_layers=2,
                         num_classes=g.num_classes)
        full = init_hgnn_params(jax.random.PRNGKey(3), cfg, spec, feat_dims)
        for p in range(2):
            rels = assignment.relations_of(p, spec)
            part = init_hgnn_params(jax.random.PRNGKey(3), cfg, spec,
                                    feat_dims, restrict_rels=rels)
            for container in ("rel", "ntype", "etype"):
                for skey, group in part[container].items():
                    for leaf, val in group.items():
                        np.testing.assert_array_equal(
                            np.asarray(val),
                            np.asarray(full[container][skey][leaf]),
                            err_msg=f"{model}/{container}/{skey}/{leaf}",
                        )


# --------------------------------------------------------------------------
# extensibility: a new HGNN variant as a pure declaration
# --------------------------------------------------------------------------


def test_new_model_is_a_pure_declaration():
    """Registering a relation module is all it takes: config validation, param
    init, the dict forward and the SPMD stacked forward all follow."""

    @register_relation_module
    class MaxPoolModule(RelationModule):
        name = "_test_maxpool"
        specs = (
            ParamSpec("w", "relation", lambda c: (c.d_src, c.hidden)),
            ParamSpec("w_self", "dst_type", lambda c: (c.d_dst, c.hidden)),
        )

        def aggregate(self, p, h_src, q_feats, mask):
            pooled = masked_mean(h_src, mask) @ p["w"]
            return pooled + q_feats @ p["w_self"]

    try:
        assert "_test_maxpool" in available_models()
        ModelConfig(model="_test_maxpool")  # registry-backed validation
        g = _GRAPH
        mp = meta_partition(g, 2, num_layers=2)
        spec = SampleSpec.from_metatree(mp.metatree, (3, 2))
        cfg = HGNNConfig(model="_test_maxpool", hidden=32, num_layers=2,
                         num_classes=g.num_classes)
        feat_dims = {t: g.feat_dim(t) for t in g.num_nodes if g.feat_dim(t)}
        params = init_hgnn_params(jax.random.PRNGKey(0), cfg, spec, feat_dims)
        params["embed"] = init_embed_tables(jax.random.PRNGKey(1), cfg,
                                            g.num_nodes, feat_dims)
        sampler = NeighborSampler(g, spec, 8, seed=1)
        batch = sampler.sample_batch(g.train_nodes[:8])
        tables = {t: jnp.asarray(f) for t, f in g.features.items()}
        arrs = batch_to_arrays(batch)
        ref = hgnn_forward(cfg, params, tables, arrs, spec)
        assert np.all(np.isfinite(np.asarray(ref)))

        # the SPMD stacking layer needs no model-specific code either
        assignment = assign_branches(spec, mp).fold(1, spec)
        plan = raf_spmd.build_plan(spec, assignment, cfg, feat_dims)
        stacks = raf_spmd.stack_params_from_dict(plan, params)
        tables_np = {t: np.asarray(f) for t, f in g.features.items()}
        tables_np.update({t: np.asarray(v) for t, v in params["embed"].items()})
        arrays = raf_spmd.stack_batch(plan, batch, tables_np)
        mesh = make_mesh((1, 1), ("data", "model"))
        loss = raf_spmd.make_loss_fn(plan, mesh)
        logits_loss = float(loss(stacks, arrays))
        assert np.isfinite(logits_loss)
    finally:
        del relmod._MODULES["_test_maxpool"]


def test_config_validation_without_registry_falls_back():
    """ModelConfig stays importable/jax-free: with the registry loaded it
    accepts exactly the registered names (plus rejects unknowns)."""
    with pytest.raises(ValueError, match="model must be one of"):
        ModelConfig(model="definitely_not_registered")
    for name in HGNN_MODELS:
        assert ModelConfig(model=name).model == name
