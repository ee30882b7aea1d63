"""HGT (Hu et al., arXiv:2003.01332), one relation's aggregation, simplified.

For a relation r = (s, e, t) at one layer, with heads i = 1..nh of width dh:

    K_i(u) = h_u W^K_{s,i}      V_i(u) = h_u W^V_{s,i}    Q_i(v) = x_v W^Q_{t,i}
    a_i(u, v) = K_i(u) W^ATT_{e,i} . Q_i(v) / sqrt(dh)
    alpha_i(., v) = softmax over the sampled neighbors u of v that exist
    AGG_r(v) = concat_i sum_u alpha_i(u, v) V_i(u) W^MSG_{e,i}

K/V projections are per source type, Q per destination type, W^ATT and
W^MSG per edge type, each per layer.  Departures from the paper, as the
configuration states them: no relation prior mu, no target-specific output
linear with its gated skip, no layer norm; the queries come from the
destination's input features (x_v), not its hidden state.  A destination
with no sampled neighbor aggregates to zero.  ``ops`` carries the matrix
products at the precisions the caller computes in: ``ops.mm`` the learned
projections, ``ops.einsum`` the per-head transforms, logits and combine.
"""

import jax
import jax.numpy as jnp

LEAVES = (
    ("wk", "src_type", lambda c: (c.d_src, c.hidden), "glorot", 1.0),
    ("wv", "src_type", lambda c: (c.d_src, c.hidden), "glorot", 1.0),
    ("wq", "dst_type", lambda c: (c.d_dst, c.hidden), "glorot", 1.0),
    ("w_att", "etype", lambda c: (c.num_heads, c.head_dim, c.head_dim), "glorot", 1.0),
    ("w_msg", "etype", lambda c: (c.num_heads, c.head_dim, c.head_dim), "glorot", 1.0),
)


def aggregate(ops, p, h, q, mask):
    """h [n, f, d_src], q [n, d_dst], mask [n, f] -> [n, hidden]."""
    nh, dh, _ = p["w_att"].shape
    n, f, _ = h.shape
    k = ops.mm(h, p["wk"]).reshape(n, f, nh, dh)
    v = ops.mm(h, p["wv"]).reshape(n, f, nh, dh)
    qq = ops.mm(q, p["wq"]).reshape(n, nh, dh)
    kw = ops.einsum("nfhd,hde->nfhe", k, p["w_att"])
    att = ops.einsum("nfhe,nhe->nfh", kw, qq) / jnp.sqrt(jnp.asarray(dh, h.dtype))
    msg = ops.einsum("nfhd,hde->nfhe", v, p["w_msg"])
    m = mask[:, :, None]
    att = jnp.where(m, att, -jnp.inf)
    top = jnp.max(att, axis=1, keepdims=True)
    z = jnp.where(m, jnp.exp(att - jax.lax.stop_gradient(
        jnp.where(jnp.isfinite(top), top, 0.0))), 0.0)
    alpha = z / jnp.maximum(jnp.sum(z, axis=1, keepdims=True), 1e-30)
    return ops.einsum("nfh,nfhd->nhd", alpha, msg).reshape(n, nh * dh)


def train_flops(c) -> float:
    """Operations of one relation at one level, forward and backward (see
    ``rgcn.train_flops`` for ``c``)."""
    n = c.n_prev * c.f
    H, dh = c.hidden, c.hidden // c.num_heads
    kv = 2.0 * (2.0 * n * c.d_src * H)
    q = 2.0 * c.n_prev * c.d_dst * H
    tr = 2.0 * (2.0 * n * H * dh)  # W_att, W_msg per head
    att = 2.0 * (2.0 * n * H)  # logits and the weighted combine
    total = 2 * kv + 2 * q + 3 * tr + 3 * att
    total += kv if c.h_grad else 0.0
    total += q if c.q_grad else 0.0
    return total


def mean_linear_calls(levels, H: int, num_heads: int):
    """Calls of the stacked mean-linear kernels in one step (counted as
    ``bench/flops.py`` says): hgt projects each level's queries with them,
    at fanout 1.  ``levels`` are ``flops._levels``'s."""
    fwd, bwd = [], []
    for d, n_prev, f, rows in levels:
        fl = sum(2.0 * n_prev * dq + 2.0 * n_prev * dq * H for _, _, dq, _, _ in rows)
        by = sum(4 * (n_prev * dq + dq * H + H + n_prev * H) + n_prev
                 for _, _, dq, _, _ in rows)
        fwd.append({"flops": fl, "bytes": by})
        g = [r for r in rows if r[4]]
        if g:
            fl = sum(2.0 * n_prev * H * dq + n_prev * dq for _, _, dq, _, _ in g)
            by = sum(4 * (n_prev * H + dq * H + n_prev * dq) + n_prev
                     for _, _, dq, _, _ in g)
            bwd.append({"flops": fl, "bytes": by})
    return {"stacked_mean_linear_pallas": fwd,
            "stacked_mean_linear_dh_pallas": bwd}


def attn_epilogue_calls(levels, H: int, nh: int):
    """Calls of the fused attention kernels in one step: the forward
    epilogue (projections of K and V, the per-head transforms, the logits,
    the masked softmax and the combine; it writes the projections out for
    the backward) and the backward to the neighbor rows."""
    dh = H // nh
    fwd, bwd = [], []
    for d, n_prev, f, rows in levels:
        n = n_prev * f
        fl = sum(2 * 2.0 * n * di * H + 2 * 2.0 * n * H * dh + 4.0 * n * H
                 for _, di, _, _, _ in rows)
        by = sum(4 * (n * di + n_prev * H + 2 * di * H + 2 * nh * dh * dh
                      + n_prev * H + 2 * n * H) + n
                 for _, di, _, _, _ in rows)
        fwd.append({"flops": fl, "bytes": by})
        g = [r for r in rows if r[3]]
        if g:
            fl = sum(2 * 2.0 * n * H * di for _, di, _, _, _ in g)
            by = sum(4 * (2 * n * H + 2 * di * H + n * di) for _, di, _, _, _ in g)
            bwd.append({"flops": fl, "bytes": by})
    return {"stacked_attn_epilogue_pallas": fwd, "stacked_attn_dh_pallas": bwd}
