"""Operations and bytes of a training step, from a cell's shapes.

Counts are algorithmic: at the sampled fanout (no padding to tiles or
blocks), at each node type's own input width (no padding to the widest),
float32 values (4 bytes) and one byte per mask entry.  A multiply-add is two
operations.  Level d of the computation tree has ``n_prev = B * f_1 ...
f_{d-1}`` parent nodes, each with ``f = f_d`` sampled neighbors.

What needs a gradient: every weight; the input of an inner level (the ReLU
of the level below); the input rows of the leaf level and the query rows
only where those rows train (``learnable`` cells, node types without
features).
"""

from __future__ import annotations

from types import SimpleNamespace
from typing import Dict, List, Optional


def _levels(setup, batch: int):
    """Per level: (d, n_prev, f, [(branch, d_src, d_dst, h_grad, q_grad)])."""
    k = setup.depth
    trained = set(setup.learnable) if setup.train_learnable else set()
    n_prev = batch
    for d in range(1, k + 1):
        f = setup.fanouts[d - 1]
        rows = []
        for b in setup.tree[d - 1]:
            leaf = d == k
            d_src = setup.dims[b.rel[0]] if leaf else setup.hidden
            h_grad = (b.rel[0] in trained) if leaf else True
            rows.append((b, d_src, setup.dims[b.rel[2]], h_grad,
                         b.rel[2] in trained))
        yield d, n_prev, f, rows
        n_prev *= f


def train_step_flops(setup, batch: int) -> float:
    """Model operations of one forward and backward pass over a batch: each
    relation's at each level (``train_flops`` of ``bench/models/<model>.py``)
    and the head's."""
    from bench.reference import model_module

    mod = model_module(setup.model)
    total = 0.0
    for d, n_prev, f, rows in _levels(setup, batch):
        for b, d_src, d_dst, h_grad, q_grad in rows:
            total += mod.train_flops(SimpleNamespace(
                n_prev=n_prev, f=f, d_src=d_src, d_dst=d_dst,
                hidden=setup.hidden, num_heads=setup.num_heads,
                h_grad=h_grad, q_grad=q_grad))
    head = 2.0 * batch * setup.hidden * setup.num_classes
    return total + 3 * head


def kernel_calls(setup, batch: int, family: str):
    """Calls of one kernel family in one training step, per kernel name,
    each call ``{"flops", "bytes"}``: ``<family>_calls`` of
    ``bench/models/<model>.py`` over the step's levels, or None where the
    model does not run that family."""
    from bench.reference import model_module

    count = getattr(model_module(setup.model), f"{family}_calls", None)
    if count is None:
        return None
    return count(list(_levels(setup, batch)), setup.hidden, setup.num_heads)


def ideal_seconds(calls: List[dict], peaks: dict) -> float:
    """Least time the chip could take: per call the larger of operations
    over peak operations and bytes over peak bandwidth, summed."""
    return sum(max(c["flops"] / peaks["flops_per_s"],
                   c["bytes"] / peaks["hbm_bytes_per_s"]) for c in calls)


def roofline_share(ctx, calls: Optional[Dict[str, List[dict]]]):
    """% of the roofline a kernel family reached in the traced steps, or
    None when the model runs no such family, or the trace holds no calls,
    or not one step's calls per step."""
    t = ctx.trace
    if t is None or ctx.peaks is None or calls is None:
        return None
    steps = t["steps"]
    spent = 0.0
    for name, want in calls.items():
        if t["kernel_calls"].get(name, 0) != len(want) * steps:
            return None
        spent += t["kernel_s"].get(name, 0.0)
    if spent <= 0:
        return None
    ideal = sum(ideal_seconds(c, ctx.peaks) for c in calls.values())
    return 100.0 * ideal * steps / spent
