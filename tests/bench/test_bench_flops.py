"""Operation and byte counts of ``bench/flops.py``: against a hand count,
and against XLA's own cost analysis of the same computation."""

from types import SimpleNamespace

import numpy as np
import pytest

from bench import flops, reference


def _tiny_setup(model: str, learnable: bool = False):
    """Schema: papers (8-d features) cite papers, authors (featureless,
    4-d rows) write papers; two layers, fanouts 3 and 2."""
    ds = {
        "num_nodes": {"paper": 10, "author": 6},
        "relations": [("author", "writes", "paper"), ("paper", "cites", "paper")],
        "target": "paper", "num_classes": 5,
        "features": {"paper": np.zeros((10, 8), np.float32)},
    }
    heta = {"data": {"fanouts": [3, 2]}, "run": {"lr": 0.01},
            "model": {"model": model, "hidden": 8, "num_heads": 2,
                      "learnable_dim": 4, "train_learnable": learnable}}
    return reference.make_setup(ds, heta, "default")


def test_rgcn_step_by_hand():
    s = _tiny_setup("rgcn")
    B, H, C = 4, 8, 5
    # level 1 (paper <- author, paper <- paper): 4 parents x 3 neighbours,
    # inputs are hidden (8) wide; level 2 under the author branch: nothing
    # (authors have no in-relations); under the paper branch: author (4-d
    # rows) and paper (8-d features), 12 parents x 2 neighbours
    lvl1 = 2 * (2 * 12 * H + 3 * (2 * 4 * H * H)) + 2 * 12 * H
    lvl2 = sum(2 * 24 * d + 2 * (2 * 12 * d * H) for d in (4, 8))
    head = 3 * (2 * B * H * C)
    assert flops.train_step_flops(s, B) == pytest.approx(lvl1 + lvl2 + head)


def test_learnable_rows_add_the_leaf_input_gradient():
    frozen = flops.train_step_flops(_tiny_setup("rgcn"), 4)
    learn = flops.train_step_flops(_tiny_setup("rgcn", learnable=True), 4)
    # the author leaf branch: its input gradient 2*12*4*8 + 24*4
    assert learn - frozen == pytest.approx(2 * 12 * 4 * 8 + 24 * 4)


def test_mean_linear_calls_by_hand():
    s = _tiny_setup("rgcn")
    calls = flops.kernel_calls(s, 4, "mean_linear")
    fwd, bwd = calls["stacked_mean_linear_pallas"], calls["stacked_mean_linear_dh_pallas"]
    assert len(fwd) == 2 and len(bwd) == 1  # the leaf inputs do not train
    assert fwd[1]["flops"] == pytest.approx(sum(2 * 24 * d + 2 * 12 * d * 8 for d in (4, 8)))
    assert fwd[1]["bytes"] == pytest.approx(
        sum(4 * (24 * d + d * 8 + 8 + 12 * 8) + 24 for d in (4, 8)))
    assert bwd[0]["flops"] == pytest.approx(2 * (2 * 4 * 8 * 8 + 12 * 8))


def _xla_flops(fn, *args):
    import jax

    cost = jax.jit(fn).lower(*args).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    return float(cost["flops"])


def test_mean_linear_forward_against_xla():
    import jax.numpy as jnp

    mod = reference.model_module("rgcn")
    ops = reference.matmul_ops("highest")
    n, f, d, H = 256, 20, 128, 64
    p = {"w": jnp.ones((d, H)), "b": jnp.zeros((H,))}
    h = jnp.ones((n, f, d))
    mask = jnp.ones((n, f), bool)
    xla = _xla_flops(lambda p, h, m: mod.aggregate(ops, p, h, None, m), p, h, mask)
    ours = 2.0 * n * f * d + 2.0 * n * d * H
    assert ours == pytest.approx(xla, rel=0.1)


def test_attention_epilogue_forward_against_xla():
    import jax.numpy as jnp

    mod = reference.model_module("hgt")
    ops = reference.matmul_ops("highest")
    n, f, d, H, nh = 128, 8, 64, 64, 4
    dh = H // nh
    p = {"wk": jnp.ones((d, H)), "wv": jnp.ones((d, H)), "wq": jnp.ones((d, H)),
         "w_att": jnp.ones((nh, dh, dh)), "w_msg": jnp.ones((nh, dh, dh))}
    h = jnp.ones((n, f, d))
    q = jnp.ones((n, d))
    mask = jnp.ones((n, f), bool)
    xla = _xla_flops(lambda *a: mod.aggregate(ops, *a), p, h, q, mask)
    # the epilogue kernel's share (K, V, the transforms, logits, combine)
    # plus the query projection the mean-linear kernel does: one level of
    # n parents with f neighbors, one relation, nothing of the input trains
    levels = [(1, n, f, [(None, d, d, False, False)])]
    epi = mod.attn_epilogue_calls(levels, H, nh)["stacked_attn_epilogue_pallas"]
    qp = mod.mean_linear_calls(levels, H, nh)["stacked_mean_linear_pallas"]
    assert epi[0]["flops"] == pytest.approx(
        2 * 2.0 * n * f * d * H + 2 * 2.0 * n * f * H * dh + 4.0 * n * f * H)
    assert epi[0]["flops"] + qp[0]["flops"] == pytest.approx(xla, rel=0.15)


def test_a_family_the_model_does_not_run_reads_nothing():
    s = _tiny_setup("rgcn")
    assert flops.kernel_calls(s, 4, "attn_epilogue") is None
    assert flops.roofline_share(
        SimpleNamespace(trace={"steps": 2, "kernel_calls": {}, "kernel_s": {}},
                        peaks={"flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}),
        None) is None
    assert set(flops.kernel_calls(_tiny_setup("hgt"), 4, "attn_epilogue")) == {
        "stacked_attn_epilogue_pallas", "stacked_attn_dh_pallas"}


def test_ideal_seconds_takes_the_larger_bound():
    peaks = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    calls = [{"flops": 200.0, "bytes": 10.0}, {"flops": 100.0, "bytes": 50.0}]
    assert flops.ideal_seconds(calls, peaks) == pytest.approx(2.0 + 5.0)
