"""R-GCN (Schlichtkrull et al., arXiv:1703.06103), one relation's aggregation.

    AGG_r(v) = mean_{u in N_r(v)} h_u  @ W_r + b_r

with the mean over the sampled in-neighbors that exist (an empty
neighborhood gives b_r).  One ``W_r``/``b_r`` per relation and layer.
``ops`` carries the matmul at the precision the caller computes in.
"""

import jax.numpy as jnp

# (leaf, scope, shape from the dims, init, scale)
LEAVES = (
    ("w", "relation", lambda c: (c.d_src, c.hidden), "glorot", 1.0),
    ("b", "relation", lambda c: (c.hidden,), "zeros", 1.0),
)


def aggregate(ops, p, h, q, mask):
    """h [n, f, d_src], q [n, d_dst] (unused), mask [n, f] -> [n, hidden]."""
    w = mask.astype(h.dtype)
    total = jnp.sum(h * w[..., None], axis=1)
    mean = total / jnp.maximum(jnp.sum(w, axis=1, keepdims=True), 1.0)
    return ops.mm(mean, p["w"]) + p["b"]


def train_flops(c) -> float:
    """Operations of one relation at one level, forward and backward: ``c``
    has the level's ``n_prev`` parents with ``f`` sampled neighbors each,
    the widths ``d_src``, ``d_dst`` and ``hidden``, and whether the inputs
    (``h_grad``) and the queries (``q_grad``) need a gradient."""
    n = c.n_prev * c.f
    mean = 2.0 * n * c.d_src
    lin = 2.0 * c.n_prev * c.d_src * c.hidden
    total = mean + lin + lin  # forward, weight gradient
    if c.h_grad:
        total += lin + n * c.d_src  # input gradient
    return total


def mean_linear_calls(levels, H: int, num_heads: int):
    """Calls of the stacked mean-linear kernels in one step, one per level
    and direction, each ``{"flops", "bytes"}`` counted as ``bench/flops.py``
    says: rgcn aggregates every level with them.  ``levels`` are
    ``flops._levels``'s."""
    fwd, bwd = [], []
    for d, n_prev, f, rows in levels:
        n = n_prev * f
        fl = sum(2.0 * n * di + 2.0 * n_prev * di * H for _, di, _, _, _ in rows)
        by = sum(4 * (n * di + di * H + H + n_prev * H) + n for _, di, _, _, _ in rows)
        fwd.append({"flops": fl, "bytes": by})
        g = [r for r in rows if r[3]]
        if g:
            fl = sum(2.0 * n_prev * H * di + n * di for _, di, _, _, _ in g)
            by = sum(4 * (n_prev * H + di * H + n * di) + n for _, di, _, _, _ in g)
            bwd.append({"flops": fl, "bytes": by})
    return {"stacked_mean_linear_pallas": fwd,
            "stacked_mean_linear_dh_pallas": bwd}
