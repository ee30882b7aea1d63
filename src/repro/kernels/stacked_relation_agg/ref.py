"""Oracles for the stacked relation-aggregation kernel family.

Two reference implementations of "run one level's AGG_r for every branch
slot of a shard":

  * :func:`stacked_agg_ref` — the **gather-then-vmap oracle**: gather each
    declared leaf's per-slot parameters through the scope index arrays
    (materializing a ``[rb, ...]`` copy of every leaf — shared parameters
    duplicated across slots) and ``vmap`` the module's ``aggregate`` over
    the branch axis.  This is the SPMD executor's historical `_agg_level`
    math, kept verbatim as the correctness oracle and the non-TPU fallback.

  * :func:`stacked_agg_grouped` — the **stacked XLA oracle**: slots grouped
    at trace time by their full (static) parameter signature; each group
    evaluates ``aggregate`` once over the merged ``[g·n]`` batch with
    *statically sliced* leaves — one weight read per unique parameter
    combination, no materialized per-slot gather.  Requires concrete
    (numpy) slot indices, so it serves benchmarks and tests rather than the
    shard_map body (where slot indices are traced per-shard data — that is
    exactly what the Pallas kernels' scalar prefetch handles).
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["stacked_agg_ref", "stacked_agg_grouped"]


def _scope_of(module) -> Dict[str, str]:
    return {s.name: s.scope for s in module.specs}


def stacked_agg_ref(module, stacks, slot_u, h, q, mask):
    """Gather-then-vmap oracle.

    stacks  {leaf: [U_scope, ...]}   one shard's per-scope parameter slabs
    slot_u  {scope: [rb] int}        per-slot index into that scope's slab
    h       [rb, n, f, d_in]         neighbor embeddings per slot
    q       [rb, n, d_dst]           destination input features per slot
    mask    [rb, n, f]               real-neighbor mask
    ->      [rb, n, hidden]
    """
    scope_of = _scope_of(module)
    p_slots = {name: stacks[name][slot_u[scope_of[name]]] for name in stacks}
    return jax.vmap(module.aggregate)(p_slots, h, q, mask)


def stacked_agg_grouped(module, stacks, slot_u_np, h, q, mask):
    """Stacked XLA oracle (static slot indices — see module docstring)."""
    scope_of = _scope_of(module)
    rb, n, f, d_in = h.shape
    groups: Dict[tuple, list] = {}
    for s in range(rb):
        sig = tuple(int(slot_u_np[sc][s]) for sc in module.scopes)
        groups.setdefault(sig, []).append(s)
    if module.fused == "mean_linear":
        # the f-reduction is weight-free and touches the bulk of the data —
        # run it once over the whole stack; only the [rb, n, d_in] means are
        # regrouped, and each unique weight is a static slice feeding one
        # flat matmul (this is the memory-movement shape the Pallas kernel
        # realizes per block on TPU).  Group outputs are concatenated and
        # un-permuted with ONE gather at the end: the earlier
        # ``out.at[sl].set`` formulation copied the whole [rb, n, d_out]
        # output once per group, which at rgcn shapes (every slot its own
        # relation ⇒ all-singleton groups) cost more than the grouping
        # saved — the 0.93x mag_l1/mag_l2 regression in BENCH_kernels.json.
        mw = mask.astype(h.dtype)
        cnt = jnp.maximum(mw.sum(-1, keepdims=True), 1.0)
        mean = jnp.sum(h * mw[..., None], axis=2) / cnt
        chunks, order = [], []
        for sig, slots in groups.items():
            u_of = dict(zip(module.scopes, sig))
            uw = u_of[scope_of["w"]]
            sl = jnp.asarray(np.asarray(slots))
            g = len(slots)
            m_g = jnp.take(mean, sl, axis=0).reshape(g * n, d_in)
            o_g = (m_g @ stacks["w"][uw] + stacks["b"][u_of[scope_of["b"]]])
            chunks.append(o_g.reshape(g, n, -1))
            order.extend(slots)
        out = jnp.concatenate(chunks, axis=0)
        inv = np.argsort(np.asarray(order))
        return jnp.take(out, jnp.asarray(inv), axis=0)
    chunks, order = [], []
    for sig, slots in groups.items():
        u_of = dict(zip(module.scopes, sig))
        p = {name: stacks[name][u_of[scope_of[name]]] for name in stacks}
        sl = jnp.asarray(np.asarray(slots))
        g = len(slots)
        hg = jnp.take(h, sl, axis=0).reshape(g * n, f, d_in)
        qg = jnp.take(q, sl, axis=0).reshape(g * n, q.shape[-1])
        mg = jnp.take(mask, sl, axis=0).reshape(g * n, f)
        chunks.append(module.aggregate(p, hg, qg, mg).reshape(g, n, -1))
        order.extend(slots)
    out = jnp.concatenate(chunks, axis=0)
    inv = np.argsort(np.asarray(order))
    return jnp.take(out, jnp.asarray(inv), axis=0)
