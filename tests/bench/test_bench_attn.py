"""The ``hgt-mag.frozen`` cell's files and readers: its configuration,
traffic and limits found by name, the operation and byte counts of the
fused attention kernels (``bench/attn_calls.py``) against a hand count, the
two rooflines read from them, and ``valid_slot_share.train`` on a tiny cell
against counts taken from the staged masks."""

from types import SimpleNamespace

import numpy as np
import pytest

import tinycell
from bench import attn_calls, dataset, flops, harness, reference


def _tiny_setup(model: str = "hgt"):
    """Schema: papers (8-d features) cite papers, authors (featureless,
    4-d rows) write papers; two layers, fanouts 3 and 2; hidden 8 in 2
    heads of 4."""
    ds = {
        "num_nodes": {"paper": 10, "author": 6},
        "relations": [("author", "writes", "paper"), ("paper", "cites", "paper")],
        "target": "paper", "num_classes": 5,
        "features": {"paper": np.zeros((10, 8), np.float32)},
    }
    heta = {"data": {"fanouts": [3, 2]}, "run": {"lr": 0.01},
            "model": {"model": model, "hidden": 8, "num_heads": 2,
                      "learnable_dim": 4, "train_learnable": False}}
    return reference.make_setup(ds, heta, "default")


def test_attention_calls_by_hand():
    """Batch 4: level 1 has 4 parents x 3 neighbors under two relations into
    paper, inputs 8 (hidden) wide and needing a gradient; level 2 has 12 x 2
    under the paper branch only (authors have no in-relations), the
    authors' 4-d rows and the papers' 8-d features, fixed."""
    calls = attn_calls.attn_calls(_tiny_setup(), 4)
    fwd, bwd = calls["stacked_attn_epilogue_pallas"], calls["stacked_attn_bwd_pallas"]
    assert len(fwd) == len(bwd) == 2
    H, dh, nh = 8, 4, 2
    # forward: K and V of every edge, W_ATT on the query and W_MSG on the
    # combined values per parent, logits and combine per edge
    f1 = 2 * (2 * 2 * 12 * 8 * H + 2 * 2 * 4 * H * dh + 2 * 2 * 12 * H)
    f2 = sum(2 * 2 * 24 * d * H + 2 * 2 * 12 * H * dh + 2 * 2 * 24 * H for d in (4, 8))
    assert fwd[0]["flops"] == pytest.approx(f1)
    assert fwd[1]["flops"] == pytest.approx(f2)
    # reads rows, mask, queries, K/V and the two transforms; writes a row
    # per parent
    b1 = 2 * (4 * (12 * 8 + 4 * H + 2 * 8 * H + 2 * nh * dh * dh + 4 * H) + 12)
    assert fwd[0]["bytes"] == pytest.approx(b1)
    # backward at level 1: the projections again, their weight gradients
    # and the rows' gradient (6 products of n x d_in x H), five per-parent
    # transforms, ten per-edge H-wide terms
    g1 = 2 * (6 * 2 * 12 * 8 * H + 5 * 2 * 4 * H * dh + 10 * 12 * H)
    # level 2: no rows' gradient (4 products)
    g2 = sum(4 * 2 * 24 * d * H + 5 * 2 * 12 * H * dh + 10 * 24 * H for d in (4, 8))
    assert bwd[0]["flops"] == pytest.approx(g1)
    assert bwd[1]["flops"] == pytest.approx(g2)
    # reads what the forward reads and the cotangent; writes the queries'
    # gradient, K/V and transform gradients and (level 1) the rows'
    c1 = 2 * (4 * (2 * 12 * 8 + 3 * 4 * H + 4 * 8 * H + 4 * nh * dh * dh) + 12)
    c2 = sum(4 * (24 * d + 3 * 12 * H + 4 * d * H + 4 * nh * dh * dh) + 24
             for d in (4, 8))
    assert bwd[0]["bytes"] == pytest.approx(c1)
    assert bwd[1]["bytes"] == pytest.approx(c2)


def test_attention_rooflines_read_the_traced_calls():
    setup = _tiny_setup()
    calls = attn_calls.attn_calls(setup, 4)
    peaks = {"flops_per_s": 1e6, "hbm_bytes_per_s": 1e5}
    trace = {"steps": 2,
             "kernel_calls": {"stacked_attn_epilogue_pallas": 4,
                              "stacked_attn_bwd_pallas": 4},
             "kernel_s": {"stacked_attn_epilogue_pallas": 0.5,
                          "stacked_attn_bwd_pallas": 2.0}}
    ctx = SimpleNamespace(setup=setup, batch=4, peaks=peaks, trace=trace)
    for metric, name, spent in (("attn_epilogue_roofline", "stacked_attn_epilogue_pallas", 0.5),
                                ("attn_bwd_roofline", "stacked_attn_bwd_pallas", 2.0)):
        want = 100.0 * flops.ideal_seconds(calls[name], peaks) * 2 / spent
        assert harness.reader(metric)(ctx) == pytest.approx(want)
    # another number of calls per step, another model, or no trace: nothing
    short = {**trace, "kernel_calls": {"stacked_attn_epilogue_pallas": 3,
                                       "stacked_attn_bwd_pallas": 4}}
    assert harness.reader("attn_epilogue_roofline")(
        SimpleNamespace(**{**vars(ctx), "trace": short})) is None
    assert harness.reader("attn_bwd_roofline")(
        SimpleNamespace(**{**vars(ctx), "setup": _tiny_setup("rgcn")})) is None
    assert harness.reader("attn_bwd_roofline")(
        SimpleNamespace(**{**vars(ctx), "trace": None})) is None


def test_the_hgt_cell_is_found_by_name():
    cell = harness.open_cell("hgt-mag.frozen")
    assert cell.config["name"] == "hgt-mag" == cell.workload["config"]
    model = cell.heta["model"]
    assert (model["model"], model["hidden"], model["num_heads"]) == ("hgt", 256, 8)
    assert model["train_learnable"] is False  # the frozen traffic, merged
    assert cell.heta["data"] == {"fanouts": [25, 20], "batch_size": 1024}
    assert set(cell.limits) == {"loss_gap", "grad_gap", "change_gap", "batch_faults"}
    per_layer = {m["name"] for m in harness.metrics_of(cell, "per_layer")}
    assert {"attn_epilogue_roofline", "attn_bwd_roofline",
            "valid_slot_share.train", "mfu.train"} <= per_layer
    rgcn = harness.open_cell("rgcn-mag.frozen")
    assert "valid_slot_share.train" in {
        m["name"] for m in harness.metrics_of(rgcn, "per_layer")}
    assert "attn_bwd_roofline" not in {
        m["name"] for m in harness.metrics_of(rgcn, "per_layer")}


@pytest.mark.parametrize("model", ["rgcn", "hgt"])
def test_valid_slot_share_is_the_staged_masks(tmp_path, model):
    """Over the window's steps, the share of staged edge slots whose mask is
    set, counted again from the batches a session built alike samples for
    those steps (padding slots staged as empty)."""
    from repro.core import raf_spmd

    seed = 13
    result = tinycell.run(tmp_path, model, seed=seed, trace=True)
    got = result["metrics"]["valid_slot_share.train"]["value"]
    cell = harness.open_cell(f"tiny-{model}.frozen", tinycell.bm(model),
                             tinycell.root_with_limits(tmp_path, model))
    ds = dataset.load(cell.config["dataset"])
    with harness.matmul_precision(cell):
        sess = harness.start_session(cell, dataset.to_hetgraph(ds), seed)
    recipe = raf_spmd.stack_recipe(sess.plan.plan)
    valid = slots = 0
    for step in range(harness.WARM_STEPS, harness.WARM_STEPS + result["attempted"]):
        batch = sess._batch_for_step(step)
        for d, sb in enumerate(recipe.slot_branch):
            mask = batch.levels[d].mask
            slots += sb.size * mask.shape[1]
            valid += sum(int(np.count_nonzero(mask[b])) for b in sb.ravel() if b >= 0)
    harness.release(sess)
    assert 0.0 < got < 1.0
    assert got == pytest.approx(valid / slots, rel=1e-12)
