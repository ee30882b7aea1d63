"""Stacked relation-aggregation kernel family: parity sweeps against the
gather-then-vmap oracle (interpret mode on CPU; TPU is the target).

Covers forward AND custom-VJP parity over non-block-multiple shapes,
all-False mask rows, dummy padding slots, shared stack rows (the HGT
pattern), the grouped "stacked XLA" oracle, the executor-level fused-path
contract (rgcn bit-equality, DESIGN.md §8) and a hypothesis-style property
test through the ``_hypothesis_compat`` shim.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.relmod import ShapeCtx, get_relation_module
from repro.kernels.ops import KernelOptions
from repro.kernels.stacked_relation_agg import (
    stacked_agg,
    stacked_agg_grouped,
    stacked_agg_ref,
    stacked_mean_linear,
    stacked_mean_linear_vmem_bytes,
    stacked_softmax_combine,
)
from repro.launch.mesh import make_mesh

rng = np.random.default_rng(7)
OPTS_ON = KernelOptions(interpret=True)


def _mean_linear_case(rb, n, f, di, do, U, seed=0, dummy_slots=()):
    r = np.random.default_rng(seed)
    w = jnp.asarray(r.standard_normal((U, di, do)) * 0.1, jnp.float32)
    b = jnp.asarray(r.standard_normal((U, do)) * 0.1, jnp.float32)
    h = jnp.asarray(r.standard_normal((rb, n, f, di)), jnp.float32)
    q = jnp.asarray(r.standard_normal((rb, n, di)), jnp.float32)
    mask = np.asarray(r.random((rb, n, f)) > 0.3)
    mask[0, 0, :] = False  # an all-False row (empty neighborhood)
    for s in dummy_slots:  # dummy padding slots: all-False masks, slot_u 0
        mask[s] = False
    slot_u = r.integers(0, U, rb)
    slot_u[list(dummy_slots)] = 0
    return h, q, jnp.asarray(mask), w, b, jnp.asarray(slot_u)


# --------------------------------------------------------------------------
# mean_linear (rgcn family)
# --------------------------------------------------------------------------

# non-block-multiple n/f/rb/d on purpose: padding paths must be exact
ML_SHAPES = [
    (5, 17, 4, 37, 24, 3),     # tiny/ragged everywhere
    (1, 1, 1, 1, 1, 1),        # degenerate minimum
    (8, 130, 3, 129, 65, 8),   # one past the n/d_out block edges
    (12, 64, 25, 128, 64, 6),  # mag-ish, shared slots (U < rb)
]


@pytest.mark.parametrize("rb,n,f,di,do,U", ML_SHAPES)
def test_stacked_mean_linear_forward_bit_equal(rb, n, f, di, do, U):
    mod = get_relation_module("rgcn")
    h, q, mask, w, b, slot_u = _mean_linear_case(rb, n, f, di, do, U, seed=rb * n)
    ref = stacked_agg_ref(mod, {"w": w, "b": b}, {"relation": slot_u}, h, q, mask)
    out = stacked_mean_linear(h, mask, w, b, slot_u, interpret=True)
    # fp32 interpret mode is bit-equal to the vmap oracle — the acceptance
    # contract of the fused path, not merely close (holds whenever d_in
    # fits one chunk, i.e. every sampled feature/hidden width ≤ block_in)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_stacked_mean_linear_forward_chunked_d_in():
    """d_in wider than block_in (donor's 789-wide features) splits the
    contraction across VMEM accumulator chunks — fp32 reassociation, so
    close (not bit-equal) to the single-matmul oracle."""
    mod = get_relation_module("rgcn")
    rb, n, f, di, do, U = 3, 200, 7, 789, 349, 2
    h, q, mask, w, b, slot_u = _mean_linear_case(rb, n, f, di, do, U, seed=600)
    ref = stacked_agg_ref(mod, {"w": w, "b": b}, {"relation": slot_u}, h, q, mask)
    out = stacked_mean_linear(h, mask, w, b, slot_u, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4, rtol=1e-5)


@pytest.mark.parametrize("dummy_slots", [(), (1, 3)])
def test_stacked_mean_linear_vjp_matches_oracle(dummy_slots):
    mod = get_relation_module("rgcn")
    rb, n, f, di, do, U = 6, 33, 5, 40, 28, 3  # shared rows: U < rb
    h, q, mask, w, b, slot_u = _mean_linear_case(
        rb, n, f, di, do, U, seed=11, dummy_slots=dummy_slots
    )
    valid = jnp.asarray([s not in dummy_slots for s in range(rb)], jnp.float32)

    def loss_fused(w_, b_, h_):
        out = stacked_mean_linear(h_, mask, w_, b_, slot_u, interpret=True)
        return jnp.sum((out * valid[:, None, None]) ** 2)

    def loss_ref(w_, b_, h_):
        out = stacked_agg_ref(mod, {"w": w_, "b": b_}, {"relation": slot_u},
                              h_, q, mask)
        return jnp.sum((out * valid[:, None, None]) ** 2)

    gf = jax.grad(loss_fused, argnums=(0, 1, 2))(w, b, h)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(w, b, h)
    for name, a, c in zip(("dw", "db", "dh"), gf, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(c), atol=1e-5, rtol=1e-5,
            err_msg=f"{name} mismatch (dummy_slots={dummy_slots})",
        )


def test_stacked_mean_linear_grad_lands_in_stack_rows():
    """Slots sharing a stack row sum their contributions into that one row
    (the custom VJP's segment-sum), and unused rows get exactly zero."""
    rb, n, f, di, do, U = 4, 9, 3, 12, 8, 3
    h, q, mask, w, b, _ = _mean_linear_case(rb, n, f, di, do, U, seed=5)
    slot_u = jnp.asarray([0, 0, 1, 1])  # row 2 unused

    def loss(w_):
        return jnp.sum(stacked_mean_linear(h, mask, w_, b, slot_u, interpret=True))

    dw = jax.grad(loss)(w)
    np.testing.assert_array_equal(np.asarray(dw[2]), np.zeros((di, do), np.float32))
    assert float(jnp.abs(dw[0]).max()) > 0 and float(jnp.abs(dw[1]).max()) > 0


@given(
    rb=st.integers(1, 6), n=st.integers(1, 40), f=st.integers(1, 6),
    di=st.integers(1, 70), do=st.integers(1, 70), U=st.integers(1, 4),
)
@settings(max_examples=10, deadline=None)
def test_stacked_mean_linear_property(rb, n, f, di, do, U):
    mod = get_relation_module("rgcn")
    h, q, mask, w, b, slot_u = _mean_linear_case(
        rb, n, f, di, do, U, seed=rb * 1000 + n * 10 + di
    )
    ref = stacked_agg_ref(mod, {"w": w, "b": b}, {"relation": slot_u}, h, q, mask)
    out = stacked_mean_linear(h, mask, w, b, slot_u, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


# --------------------------------------------------------------------------
# softmax_combine epilogue (rgat/hgt family)
# --------------------------------------------------------------------------


def _attn_case(rb, n, f, nh, dh, seed=0):
    r = np.random.default_rng(seed)
    e = jnp.asarray(r.standard_normal((rb, n, f, nh)), jnp.float32)
    v = jnp.asarray(r.standard_normal((rb, n, f, nh, dh)), jnp.float32)
    mask = np.asarray(r.random((rb, n, f)) > 0.3)
    mask[0, 0, :] = False
    return e, jnp.asarray(mask), v


@pytest.mark.parametrize("rb,n,f,nh,dh", [
    (3, 21, 4, 2, 5),
    (1, 1, 1, 1, 1),
    (5, 130, 3, 4, 16),
])
def test_stacked_softmax_combine_parity(rb, n, f, nh, dh):
    from repro.core.relmod import masked_softmax

    e, mask, v = _attn_case(rb, n, f, nh, dh, seed=n)
    alpha = masked_softmax(e, mask[:, :, :, None], axis=2)
    ref = jnp.einsum("rnfh,rnfhd->rnhd", alpha, v).reshape(rb, n, nh * dh)
    out = stacked_softmax_combine(e, mask, v, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6, rtol=1e-6)

    def loss_fused(e_, v_):
        return jnp.sum(stacked_softmax_combine(e_, mask, v_, interpret=True) ** 2)

    def loss_ref(e_, v_):
        a = masked_softmax(e_, mask[:, :, :, None], axis=2)
        return jnp.sum(jnp.einsum("rnfh,rnfhd->rnhd", a, v_) ** 2)

    gf = jax.grad(loss_fused, argnums=(0, 1))(e, v)
    gr = jax.grad(loss_ref, argnums=(0, 1))(e, v)
    for name, a, c in zip(("de", "dv"), gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), atol=1e-5,
                                   rtol=1e-5, err_msg=name)


# --------------------------------------------------------------------------
# full dispatch: every registered model, fused vs oracle vs grouped
# --------------------------------------------------------------------------


def _module_case(model, rb, n, f, di, dd, hidden, nh, seed=0):
    r = np.random.default_rng(seed)
    mod = get_relation_module(model)
    sc = ShapeCtx(hidden, nh, hidden // nh, di, dd)
    U_of = {s: u for s, u in zip(mod.scopes, (3, 2, 5, 4))}
    stacks = {
        s.name: jnp.asarray(
            r.standard_normal((U_of[s.scope],) + tuple(s.shape(sc))) * 0.1,
            jnp.float32,
        )
        for s in mod.specs
    }
    slot_np = {s: r.integers(0, U_of[s], rb) for s in mod.scopes}
    slot_u = {s: jnp.asarray(v) for s, v in slot_np.items()}
    h = jnp.asarray(r.standard_normal((rb, n, f, di)), jnp.float32)
    q = jnp.asarray(r.standard_normal((rb, n, dd)), jnp.float32)
    mask = np.asarray(r.random((rb, n, f)) > 0.3)
    mask[0, 1, :] = False
    return mod, stacks, slot_np, slot_u, h, q, jnp.asarray(mask)


@pytest.mark.parametrize("model", ["rgcn", "rgat", "hgt"])
def test_stacked_agg_fused_and_grouped_match_oracle(model):
    mod, stacks, slot_np, slot_u, h, q, mask = _module_case(
        model, rb=5, n=19, f=4, di=23, dd=17, hidden=32, nh=4, seed=3
    )
    ref = stacked_agg_ref(mod, stacks, slot_u, h, q, mask)
    out = stacked_agg(mod, stacks, slot_u, h, q, mask, opts=OPTS_ON)
    grp = stacked_agg_grouped(mod, stacks, slot_np, h, q, mask)
    tol = 0 if model == "rgcn" else 1e-5
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=tol)
    np.testing.assert_allclose(np.asarray(grp), np.asarray(ref), atol=1e-6)

    def loss_fused(st, h_):
        return jnp.sum(stacked_agg(mod, st, slot_u, h_, q, mask, opts=OPTS_ON) ** 2)

    def loss_ref(st, h_):
        return jnp.sum(stacked_agg_ref(mod, st, slot_u, h_, q, mask) ** 2)

    gf = jax.grad(loss_fused, argnums=(0, 1))(stacks, h)
    gr = jax.grad(loss_ref, argnums=(0, 1))(stacks, h)
    for a, c in zip(jax.tree.leaves(gf), jax.tree.leaves(gr)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), atol=1e-5, rtol=1e-5)


# --------------------------------------------------------------------------
# fused attention epilogue: the fuse_epilogue toggle selects between the
# fully fused kernel and the attn_parts factoring — both must match the
# gather-then-vmap oracle, forward AND VJP (DESIGN.md §8)
# --------------------------------------------------------------------------

OPTS_PARTS = KernelOptions(interpret=True, fuse_epilogue=False)


@pytest.mark.parametrize("model", ["rgat", "hgt"])
@pytest.mark.parametrize("rb,n,f", [
    (5, 19, 4),    # non-block-multiple everywhere
    (3, 130, 3),   # one past the n block edge
])
def test_fused_epilogue_matches_attn_parts_and_oracle(model, rb, n, f):
    """The fused epilogue (per-slot projections streamed from the weight
    stacks) and the attn_parts oracle factoring agree with the vmap oracle
    at non-block-multiple shapes — forward and gradients, including stacks
    with shared rows (U < rb forces slot collisions)."""
    mod, stacks, slot_np, slot_u, h, q, mask = _module_case(
        model, rb=rb, n=n, f=f, di=23, dd=17, hidden=32, nh=4, seed=rb * n
    )
    # force shared stack rows: at least two slots per scope hit row 0
    slot_u = {s: jnp.asarray(np.where(np.arange(rb) < 2, 0, v))
              for s, v in slot_np.items()}

    ref = stacked_agg_ref(mod, stacks, slot_u, h, q, mask)
    fused = stacked_agg(mod, stacks, slot_u, h, q, mask, opts=OPTS_ON)
    parts = stacked_agg(mod, stacks, slot_u, h, q, mask, opts=OPTS_PARTS)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(parts), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)

    def loss(opts):
        def f_(st, h_):
            return jnp.sum(stacked_agg(mod, st, slot_u, h_, q, mask,
                                       opts=opts) ** 2)
        return f_

    g_fused = jax.grad(loss(OPTS_ON), argnums=(0, 1))(stacks, h)
    g_parts = jax.grad(loss(OPTS_PARTS), argnums=(0, 1))(stacks, h)
    g_ref = jax.grad(
        lambda st, h_: jnp.sum(stacked_agg_ref(mod, st, slot_u, h_, q, mask) ** 2),
        argnums=(0, 1),
    )(stacks, h)
    for a, b, c in zip(jax.tree.leaves(g_fused), jax.tree.leaves(g_parts),
                       jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   atol=2e-5, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(b), np.asarray(c),
                                   atol=2e-5, rtol=1e-5)


def test_fused_epilogue_grad_lands_in_stack_rows():
    """Slots sharing a projection-stack row sum their gradient contributions
    into that row (the custom VJP's stack-form gradients), and rows no slot
    references get exactly zero — the contract sync_stack_grads relies on."""
    mod, stacks, slot_np, _, h, q, mask = _module_case(
        "rgat", rb=4, n=11, f=3, di=12, dd=10, hidden=16, nh=4, seed=9
    )
    # every scope: slots 0-1 share row 0, slots 2-3 share row 1; higher rows
    # stay unused (every scope's stack has ≥2 rows in _module_case)
    slot_u = {s: jnp.asarray([0, 0, 1, 1]) for s in mod.scopes}

    def loss(st):
        return jnp.sum(stacked_agg(mod, st, slot_u, h, q, mask, opts=OPTS_ON))

    g = jax.grad(loss)(stacks)
    scope_of = {sp.name: sp.scope for sp in mod.specs}
    for name, gs in g.items():
        u_used = np.unique(np.asarray(slot_u[scope_of[name]]))
        for u in range(gs.shape[0]):
            mag = float(jnp.abs(gs[u]).max())
            if u not in u_used:
                assert mag == 0.0, f"{name}[{u}] unused but got grad {mag}"


@pytest.mark.parametrize("model", ["rgat", "hgt"])
def test_session_3step_loss_parity_fused_vs_attn_parts(model):
    """Executor-level acceptance: a 3-step training run through the fused
    epilogue produces the same losses as the attn_parts oracle factoring
    (≤1e-5), end to end through the api session."""
    from repro.api import DataConfig, Heta, HetaConfig, ModelConfig
    from repro.api import PartitionConfig, RunConfig

    def run(fuse):
        cfg = HetaConfig(
            data=DataConfig(dataset="ogbn-mag", scale=0.002, fanouts=(3, 2),
                            batch_size=16),
            partition=PartitionConfig(num_partitions=2),
            model=ModelConfig(model=model, hidden=32),
            run=RunConfig(executor="raf_spmd", steps=3, lr=1e-2, seed=0),
        ).updated(kernels=dict(interpret=True, fuse_epilogue=fuse))
        return np.asarray(Heta(cfg).run()["losses"])

    fused, parts = run(True), run(False)
    assert fused.shape == (3,) and np.isfinite(fused).all()
    np.testing.assert_allclose(fused, parts, atol=1e-5, rtol=1e-6)


def test_hgt_published_widths_fit_matches_vanilla():
    """HGT at its published widths (hidden 256, 8 heads) on a tiny ogbn-mag
    graph, through ``Heta.fit`` on ``raf_spmd`` with the Pallas kernels in
    interpret mode (the new backward among them), against the ``vanilla``
    oracle: the losses of three steps, and the first gradient of every
    weight, read back from Adam's first moment after one step."""
    from repro.api import DataConfig, Heta, HetaConfig, ModelConfig
    from repro.api import PartitionConfig, RunConfig
    from repro.core.relmod import SCOPE_CONTAINER

    def session(executor):
        cfg = HetaConfig(
            data=DataConfig(dataset="ogbn-mag", scale=0.002, fanouts=(3, 2),
                            batch_size=16),
            partition=PartitionConfig(num_partitions=2),
            model=ModelConfig(model="hgt", hidden=256, num_heads=8,
                              train_learnable=False),
            run=RunConfig(executor=executor, steps=3, lr=1e-3, seed=0),
        ).updated(kernels=dict(interpret=True))
        sess = Heta(cfg)
        sess.build_graph()
        sess.partition()
        sess.profile_and_cache()
        sess.compile()
        sess.fit(steps=1)
        m1 = jax.tree.map(np.asarray, sess.state["opt"]["m"])
        sess.fit(steps=2)
        return sess, m1

    spmd, m_spmd = session("raf_spmd")
    vanilla, m_van = session("vanilla")
    np.testing.assert_allclose(spmd.losses, vanilla.losses, atol=1e-5, rtol=1e-5)

    plan = spmd.plan.plan
    checked = 0
    for layer in plan.layers:
        for spec_ in plan.module.specs:
            for p, row in enumerate(plan.scope_keys[(spec_.scope, layer)]):
                for u, key in enumerate(row):
                    want = m_van[SCOPE_CONTAINER[spec_.scope]][key][spec_.name]
                    got = m_spmd[f"layer{layer}"][spec_.name][p, u]
                    got = got[tuple(slice(0, s) for s in want.shape)]
                    np.testing.assert_allclose(
                        got, want, atol=1e-6, rtol=1e-4,
                        err_msg=f"first gradient of {key}/{spec_.name}")
                    checked += 1
    assert checked >= 2 * len(plan.module.specs)
    np.testing.assert_allclose(m_spmd["head"]["w"], m_van["head"]["w"],
                               atol=1e-6, rtol=1e-4)


def test_stacked_agg_disabled_is_oracle():
    mod, stacks, slot_np, slot_u, h, q, mask = _module_case(
        "rgcn", rb=3, n=8, f=3, di=10, dd=10, hidden=16, nh=4, seed=4
    )
    off = stacked_agg(mod, stacks, slot_u, h, q, mask,
                      opts=KernelOptions(enabled=False))
    ref = stacked_agg_ref(mod, stacks, slot_u, h, q, mask)
    np.testing.assert_array_equal(np.asarray(off), np.asarray(ref))


def test_stacked_agg_broken_family_contract_raises():
    """A module that declares the mean-linear kernel family without its
    contract (``w`` and ``b`` in one scope) is refused, never silently run
    through the oracle while the kernels are selected."""
    from repro.core.relmod import ParamSpec, RelationModule

    class Broken(RelationModule):
        name = "_broken_mean_linear"
        fused = "mean_linear"
        specs = (
            ParamSpec("w", "relation", lambda c: (c.d_src, c.hidden)),
            ParamSpec("b", "dst_type", lambda c: (c.hidden,), init="zeros"),
        )

    h, q, mask, w, b, slot_u = _mean_linear_case(2, 8, 3, 10, 16, 2)
    with pytest.raises(ValueError, match="contract"):
        stacked_agg(Broken(), {"w": w, "b": b},
                    {"relation": slot_u, "dst_type": slot_u}, h, q, mask,
                    opts=OPTS_ON)


def test_vmem_budget():
    """Static VMEM per grid step stays under the 16 MiB budget at the
    paper's largest shapes (IGB-HET feature width, fanout 25)."""
    assert stacked_mean_linear_vmem_bytes(25600, 25, 1024, 64) <= 16 * 2**20
    assert stacked_mean_linear_vmem_bytes(4096, 25, 789, 349) <= 16 * 2**20


# --------------------------------------------------------------------------
# executor level: the raf_spmd fused forward is bit-equal for rgcn
# --------------------------------------------------------------------------


def test_raf_spmd_fused_forward_bit_equal_rgcn():
    """`raf_spmd` forward through the fused path (interpret mode) is
    bit-equal to the vmap path for rgcn — the executor-level acceptance
    contract on top of the op-level sweeps above."""
    from repro.core import raf_spmd
    from repro.core.hgnn import HGNNConfig, batch_to_arrays
    from repro.core.meta_partition import meta_partition
    from repro.core.raf import assign_branches
    from repro.graph.sampler import NeighborSampler, SampleSpec
    from repro.graph.synthetic import ogbn_mag_like
    from jax.sharding import PartitionSpec as P

    g = ogbn_mag_like(scale=0.002)
    mp = meta_partition(g, 2, num_layers=2)
    spec = SampleSpec.from_metatree(mp.metatree, (4, 3))
    b = NeighborSampler(g, spec, 8, seed=1).sample_batch(g.train_nodes[:8])
    cfg = HGNNConfig(model="rgcn", hidden=32, num_layers=2,
                     num_classes=g.num_classes)
    feat_dims = {t: g.feat_dim(t) for t in g.num_nodes if g.feat_dim(t)}
    params = __import__("repro.core.hgnn", fromlist=["init_hgnn_params"]).init_hgnn_params(
        jax.random.PRNGKey(0), cfg, spec, feat_dims)

    assignment = assign_branches(spec, mp).fold(1, spec)
    plan = raf_spmd.build_plan(spec, assignment, cfg, feat_dims)
    stacks = raf_spmd.stack_params_from_dict(plan, params)
    tables = {t: np.asarray(f) for t, f in g.features.items()}
    for t in g.num_nodes:
        if t not in tables:
            tables[t] = np.zeros((g.num_nodes[t], cfg.learnable_dim), np.float32)
    arrays = raf_spmd.stack_batch(plan, b, tables)

    mesh = make_mesh((1, 1), ("data", "model"))
    arr_specs = raf_spmd._array_specs(plan, ("data",), "model")
    rel_specs = {k: v for k, v in raf_spmd._stack_specs(plan).items() if k != "head"}
    feats = {k: v for k, v in arrays.items() if "feat" in k}
    rest = {k: v for k, v in arrays.items() if "feat" not in k}

    def run(kernels):
        def body(st, fe, re_):
            return raf_spmd.raf_spmd_forward(plan, st, {**fe, **re_}, "model",
                                             True, kernels)
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(rel_specs, {k: arr_specs[k] for k in feats},
                      {k: arr_specs[k] for k in rest}),
            out_specs=P(("data",), None),
            check_vma=False,
        )({k: v for k, v in stacks.items() if k != "head"}, feats, rest)

    vmap_root = run(KernelOptions(enabled=False))
    fused_root = run(KernelOptions(interpret=True))
    np.testing.assert_array_equal(np.asarray(fused_root), np.asarray(vmap_root))


# --------------------------------------------------------------------------
# the attention backward (stacked_attn_bwd_pallas): against jax.vjp of the
# plain jnp aggregation, and what it keeps and builds
# --------------------------------------------------------------------------


def _attn_bwd_case(model, nh, dh, rb=4, n=21, f=5, di=23, dd=17, seed=0):
    """Non-block-multiple shapes (21 rows in blocks of 8, fanout 5 padded to
    8, input width 23 in chunks of 16), an empty neighborhood in every slot,
    and slots 0 and 1 sharing every stack row."""
    mod, stacks, slot_np, _, h, q, mask = _module_case(
        model, rb=rb, n=n, f=f, di=di, dd=dd, hidden=nh * dh, nh=nh, seed=seed)
    m = np.asarray(mask).copy()
    m[:, 3, :] = False
    slot_u = {s: jnp.asarray(np.where(np.arange(rb) < 2, 0, v))
              for s, v in slot_np.items()}
    return mod, stacks, slot_u, h, q, jnp.asarray(m)


ATTN_BWD_CASES = [
    ("hgt", 8, 32, True),
    ("hgt", 8, 32, False),
    ("hgt", 4, 16, True),
    ("hgt", 4, 16, False),
    ("rgat", 4, 16, True),
    ("rgat", 4, 16, False),
]


@pytest.mark.parametrize("model,nh,dh,h_grad", ATTN_BWD_CASES)
def test_attn_bwd_matches_aggregate_vjp(model, nh, dh, h_grad):
    """The fused epilogue's backward equals ``jax.vjp`` of the module's plain
    ``aggregate`` (vmapped over the slots) for every weight, the queries and,
    where they are differentiated (``h_grad``), the neighbor rows."""
    mod, stacks, slot_u, h, q, mask = _attn_bwd_case(model, nh, dh)
    g = jnp.asarray(np.random.default_rng(1).standard_normal(
        (h.shape[0], h.shape[1], nh * dh)), jnp.float32)

    def fused(st, h_, q_):
        return stacked_agg(mod, st, slot_u, h_, q_, mask, opts=OPTS_ON,
                           block_n=8, block_in=16)

    def plain(st, h_, q_):
        return stacked_agg_ref(mod, st, slot_u, h_, q_, mask)

    with jax.default_matmul_precision("highest"):
        if h_grad:
            out_f, vjp_f = jax.vjp(fused, stacks, h, q)
            out_r, vjp_r = jax.vjp(plain, stacks, h, q)
        else:
            out_f, vjp_f = jax.vjp(lambda st, q_: fused(st, h, q_), stacks, q)
            out_r, vjp_r = jax.vjp(lambda st, q_: plain(st, h, q_), stacks, q)
        gf, gr = vjp_f(g), vjp_r(g)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_r),
                               atol=1e-5, rtol=1e-5)
    names = ["stacks/" + "/".join(str(k.key) for k in path)
             for path, _ in jax.tree_util.tree_flatten_with_path(gr[0])[0]]
    names += ["h", "q"] if h_grad else ["q"]
    leaves_f = jax.tree.leaves(gf)
    leaves_r = jax.tree.leaves(gr)
    assert len(leaves_f) == len(leaves_r) == len(names)
    for name, a, c in zip(names, leaves_f, leaves_r):
        scale = float(np.abs(np.asarray(c)).max())
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   atol=2e-5 * max(scale, 1.0), rtol=1e-4,
                                   err_msg=f"{model} {nh}x{dh} d{name}")


def _subjaxprs(eqn):
    """The jaxprs in an equation's parameters (jit, shard_map, custom_vjp
    bodies, ...)."""
    for v in eqn.params.values():
        for x in v if isinstance(v, (tuple, list)) else (v,):
            if isinstance(x, jax.extend.core.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, jax.extend.core.Jaxpr):
                yield x


def _bwd_pallas_eqns(jaxpr):
    """The ``stacked_attn_bwd_pallas`` calls anywhere in a closed jaxpr."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            if eqn.params.get("name") == "stacked_attn_bwd_pallas":
                out.append(eqn)
            continue
        for sub in _subjaxprs(eqn):
            out.extend(_bwd_pallas_eqns(sub))
    return out


@pytest.mark.parametrize("h_grad", [True, False])
def test_attn_bwd_writes_row_gradients_only_where_rows_train(h_grad):
    """The neighbor-row gradient is an output of the backward only when the
    rows are differentiated (the leaf level of a cell whose features stay
    fixed is not): one kernel call, with or without a ``[rb, n, f, d_in]``
    output."""
    mod, stacks, slot_u, h, q, mask = _attn_bwd_case("hgt", 4, 16)

    def loss(st, h_):
        return jnp.sum(stacked_agg(mod, st, slot_u, h_, q, mask, opts=OPTS_ON,
                                   block_n=8) ** 2)

    fn = jax.grad(loss, argnums=(0, 1) if h_grad else 0)
    calls = _bwd_pallas_eqns(jax.make_jaxpr(fn)(stacks, h).jaxpr)
    assert len(calls) == 1
    rows = [v for v in calls[0].outvars if v.aval.ndim == 4]
    assert len(rows) == int(h_grad)



@pytest.mark.parametrize("learn_feats", [False, True])
def test_train_step_writes_leaf_row_gradients_only_where_features_train(
        learn_feats):
    """Through the whole SPMD train step (shard_map, jit), the attention
    backward sees which rows are differentiated: the inner level's always,
    the leaf level's feature rows only where the features train."""
    from repro.core import raf_spmd
    from repro.core.hgnn import HGNNConfig, init_hgnn_params
    from repro.core.meta_partition import meta_partition
    from repro.core.raf import assign_branches
    from repro.graph.sampler import NeighborSampler, SampleSpec
    from repro.graph.synthetic import ogbn_mag_like
    from repro.optim.adam import AdamConfig, adam_init

    g = ogbn_mag_like(scale=0.002)
    mp = meta_partition(g, 2, num_layers=2)
    spec = SampleSpec.from_metatree(mp.metatree, (3, 2))
    b = NeighborSampler(g, spec, 8, seed=1).sample_batch(g.train_nodes[:8])
    cfg = HGNNConfig(model="hgt", hidden=32, num_heads=4, num_layers=2,
                     num_classes=g.num_classes)
    feat_dims = {t: g.feat_dim(t) for t in g.num_nodes if g.feat_dim(t)}
    params = init_hgnn_params(jax.random.PRNGKey(0), cfg, spec, feat_dims)
    plan = raf_spmd.build_plan(spec, assign_branches(spec, mp).fold(1, spec),
                               cfg, feat_dims)
    stacks = raf_spmd.stack_params_from_dict(plan, params)
    tables = {t: np.asarray(f) for t, f in g.features.items()}
    for t in g.num_nodes:
        tables.setdefault(
            t, np.zeros((g.num_nodes[t], cfg.learnable_dim), np.float32))
    arrays = raf_spmd.stack_batch(plan, b, tables)
    step = raf_spmd.make_train_step(
        plan, make_mesh((1, 1), ("data", "model")), AdamConfig(),
        learn_feats=learn_feats, kernels=OPTS_ON)
    calls = _bwd_pallas_eqns(
        jax.make_jaxpr(step)(stacks, adam_init(stacks), arrays).jaxpr)
    assert len(calls) == 2  # one per level
    with_rows = sum(any(v.aval.ndim == 4 for v in c.outvars) for c in calls)
    assert with_rows == 1 + int(learn_feats)

def _float_arrays(tree):
    return [a for a in jax.tree.leaves(tree)
            if hasattr(a, "dtype") and jnp.issubdtype(a.dtype, jnp.floating)]


def test_attn_vjp_residuals_hold_nothing_per_edge():
    """``_ae_vjp_fwd`` saves the kernel's own inputs, not the projections: no
    residual of the whole aggregation (the q-side projection's included)
    has ``rb*n*f*H`` elements, one row per edge slot and ``H`` columns, or
    more — with the input rows narrower than ``H``, only such an array
    could."""
    from jax.custom_derivatives import CustomVJPPrimal

    from repro.kernels.stacked_relation_agg import ops as agg_ops

    mod, stacks, slot_u, h, q, mask = _attn_bwd_case("hgt", 4, 16, di=23)
    rb, n, f, _ = h.shape
    H = 64
    _, vjp_fn = jax.vjp(
        lambda st, h_: stacked_agg(mod, st, slot_u, h_, q, mask, opts=OPTS_ON),
        stacks, h)
    sizes = [a.size for a in _float_arrays(vjp_fn)]
    assert sizes and max(sizes) < rb * n * f * H, sorted(sizes)[-3:]

    # and _ae_vjp_fwd's own residuals
    epi = mod.attn_epilogue(stacks, slot_u, q, linear=lambda w, u, x: jnp.einsum(
        "rnd,rdk->rnk", x, w[u]))
    us = jnp.stack([slot_u["src_type"], slot_u["src_type"], slot_u["etype"]])
    cfg = agg_ops._AECfg(bn=8, bc=23, bb=8, nh=4, dh=16, scale=0.25, slope=None,
                         has_eb=False, has_post=True, shared_v=False,
                         interpret=True)
    dummy = jnp.zeros((1, 1, 1), jnp.float32)
    _, res = agg_ops._ae_vjp_fwd(cfg, *(
        CustomVJPPrimal(x, True)
        for x in (h, mask, epi.qv, dummy, epi.we, epi.wv, epi.pe, epi.pv, us)))
    sizes = [a.size for a in _float_arrays(res)]
    assert max(sizes) < rb * n * f * H, sorted(sizes)[-3:]


def test_attn_bwd_builds_nothing_per_edge_outside_its_kernel():
    """Outside the Pallas calls, the gradient program builds no float array
    with ``rb*n*f*H`` elements or more (the input rows, narrower than ``H``,
    and their padded copy are the largest)."""
    mod, stacks, slot_u, h, q, mask = _attn_bwd_case("hgt", 4, 16, di=23)
    rb, n, f, _ = h.shape
    H = 64

    def loss(st, h_):
        return jnp.sum(stacked_agg(mod, st, slot_u, h_, q, mask, opts=OPTS_ON) ** 2)

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                continue
            for v in eqn.outvars:
                aval = v.aval
                if (hasattr(aval, "dtype") and jnp.issubdtype(aval.dtype, jnp.floating)
                        and np.prod(aval.shape) >= rb * n * f * H):
                    raise AssertionError(f"{eqn.primitive.name} builds {aval}")
            for sub in _subjaxprs(eqn):
                walk(sub)

    walk(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(stacks, h).jaxpr)


@pytest.mark.parametrize("model,f,d_in", [
    ("hgt", 20, 128), ("hgt", 25, 256), ("hgt", 24, 128), ("hgt", 32, 256),
    ("rgat", 20, 128), ("rgat", 25, 256),
])
def test_attn_bwd_vmem_fits_at_the_cells_shapes(model, f, d_in):
    """At ogbn-mag's shapes under HGT's published widths (H 256, 8 heads;
    25,600 parents with fanout 20 at input width 128, 1,024 with fanout 25
    at 256, padded to 24 and 32), the backward's node block keeps its working
    set within its budget, half the scoped VMEM both attention kernels ask
    for, and stays a whole number of sublane tiles that divides the
    forward's."""
    from repro.kernels.stacked_relation_agg import (
        stacked_attn_bwd_block,
        stacked_attn_bwd_vmem_bytes,
    )
    from repro.kernels.stacked_relation_agg.ops import _BWD_VMEM_BUDGET

    kw = dict(shared_v=model == "rgat", has_post=model == "hgt",
              has_eb=model == "rgat")
    n = 25600 if d_in == 128 else 1024
    bb = stacked_attn_bwd_block(n, f, d_in, 8, 32, block_n=128, **kw)
    assert bb % 8 == 0 and 128 % bb == 0
    used = stacked_attn_bwd_vmem_bytes(n, f, d_in, 8, 32, block_n=bb, **kw)
    assert used <= _BWD_VMEM_BUDGET
    # the halving is what brings it under: a twice larger block would not fit
    # wherever the block was cut
    if bb < 128:
        assert stacked_attn_bwd_vmem_bytes(
            n, f, d_in, 8, 32, block_n=2 * bb, **kw) > _BWD_VMEM_BUDGET
