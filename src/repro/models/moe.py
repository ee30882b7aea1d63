"""Mixture-of-Experts MLP: top-k routing with capacity-based dispatch.

Baseline formulation (GSPMD-partitionable): tokens rank themselves into
per-expert capacity slots via a cumulative-sum over the top-k assignment
mask, are gathered into [E, C, D] expert batches, run the gated-SiLU expert
FFN as a batched einsum with the expert axis sharded over ``"model"``, and
are combined back with their router weights.  FLOPs are proportional to
*active* parameters (top-k · capacity_factor), not total experts.

This is structurally Heta's RAF paradigm (DESIGN.md §4): experts ≡
relations, the per-expert FFN ≡ relation-specific aggregation computed where
its parameters live, and the weighted combine ≡ the cross-relation
aggregation; the token movement is the partial-aggregation exchange.

An explicit shard_map expert-parallel variant (all_to_all token exchange) is
the §Perf hillclimb; see ``moe_shard_map`` below.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.layers import he_init, rms_norm

__all__ = ["moe_params", "moe_block", "mlp_params", "mlp_block", "router_stats"]


def mlp_params(key: jax.Array, cfg: ArchConfig, dtype) -> Dict:
    D, F = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "w1": he_init(ks[0], (D, F), dtype, fan_in=D),
        "w3": he_init(ks[1], (D, F), dtype, fan_in=D),
        "w2": he_init(ks[2], (F, D), dtype, fan_in=F),
        "norm": jnp.ones((D,), dtype),
    }


def mlp_block(p: Dict, cfg: ArchConfig, x: jnp.ndarray) -> jnp.ndarray:
    h = rms_norm(x, p["norm"], cfg.norm_eps)
    return x + (jax.nn.silu(h @ p["w1"]) * (h @ p["w3"])) @ p["w2"]


def moe_params(key: jax.Array, cfg: ArchConfig, dtype) -> Dict:
    D, E, F = cfg.d_model, cfg.moe_experts, cfg.expert_ff
    ks = jax.random.split(key, 4)
    return {
        "router": he_init(ks[0], (D, E), jnp.float32, fan_in=D),
        "w1": he_init(ks[1], (E, D, F), dtype, fan_in=D),
        "w3": he_init(ks[2], (E, D, F), dtype, fan_in=D),
        "w2": he_init(ks[3], (E, F, D), dtype, fan_in=F),
        "norm": jnp.ones((D,), dtype),
    }


def _route(cfg: ArchConfig, h: jnp.ndarray, router: jnp.ndarray):
    """Top-k routing.  h [T, D] -> (expert_idx [T, k], weights [T, k], probs)."""
    logits = h.astype(jnp.float32) @ router
    probs = jax.nn.softmax(logits, axis=-1)  # [T, E]
    weights, idx = jax.lax.top_k(probs, cfg.moe_topk)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    return idx, weights, probs


def _capacity(cfg: ArchConfig, T: int) -> int:
    c = int(T * cfg.moe_topk * cfg.capacity_factor / cfg.moe_experts)
    return max(8, -(-c // 8) * 8)  # round up to 8


def moe_block(
    p: Dict, cfg: ArchConfig, x: jnp.ndarray, return_aux: bool = False
):
    """x [b, s, D] -> [b, s, D] with top-k expert FFNs (dropping at capacity)."""
    b, s, D = x.shape
    T = b * s
    E, K = cfg.moe_experts, cfg.moe_topk
    C = _capacity(cfg, T)
    h = rms_norm(x, p["norm"], cfg.norm_eps).reshape(T, D)

    idx, weights, probs = _route(cfg, h, p["router"])  # [T, K]

    # position of each (token, k) within its expert's capacity
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)  # [T, K, E]
    flat = onehot.reshape(T * K, E)
    pos_in_e = jnp.cumsum(flat, axis=0) - flat  # rank among same-expert picks
    pos = (pos_in_e * flat).sum(-1).reshape(T, K)  # [T, K]
    keep = pos < C

    # scatter token ids into [E, C] slots (dropped tokens never land)
    slot_e = idx.reshape(-1)  # [T*K]
    slot_c = pos.reshape(-1)
    tok = jnp.repeat(jnp.arange(T), K)
    ok = keep.reshape(-1)
    slot_c = jnp.where(ok, slot_c, C)  # overflow bucket, sliced off
    gather_idx = jnp.zeros((E, C + 1), jnp.int32).at[slot_e, slot_c].set(
        tok.astype(jnp.int32), mode="drop"
    )[:, :C]
    slot_used = jnp.zeros((E, C + 1), jnp.bool_).at[slot_e, slot_c].set(
        ok, mode="drop"
    )[:, :C]

    xe = h[gather_idx] * slot_used[..., None].astype(h.dtype)  # [E, C, D]
    act = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, p["w1"])) * jnp.einsum(
        "ecd,edf->ecf", xe, p["w3"]
    )
    ye = jnp.einsum("ecf,efd->ecd", act, p["w2"])  # [E, C, D]

    # combine: scatter-add expert outputs back to tokens, weighted
    w_slot = jnp.zeros((E, C + 1), jnp.float32).at[slot_e, slot_c].set(
        weights.reshape(-1), mode="drop"
    )[:, :C]
    contrib = ye * w_slot[..., None].astype(ye.dtype)
    out = jnp.zeros((T, D), ye.dtype).at[gather_idx.reshape(-1)].add(
        contrib.reshape(E * C, D)
    )
    y = x + out.reshape(b, s, D)
    if return_aux:
        # load-balance auxiliaries (Switch-style): fraction per expert
        me = probs.mean(0)
        ce = jax.nn.one_hot(idx[:, 0], E).mean(0)
        aux = E * jnp.sum(me * ce)
        return y, {"aux_loss": aux, "dropped": 1.0 - slot_used.mean()}
    return y


# --------------------------------------------------------------------------
# expert-parallel MoE (the §Perf hillclimb; RAF applied to experts)
# --------------------------------------------------------------------------


def moe_block_ep(
    p: Dict,
    cfg: ArchConfig,
    x: jnp.ndarray,
    mesh,
    dp_axes,
    model_axis: str = "model",
) -> jnp.ndarray:
    """Expert-parallel MoE via shard_map + all_to_all — Heta's RAF paradigm
    applied to experts (DESIGN.md §4): each model shard owns E/MP experts'
    parameters, tokens are routed *locally per shard* (capacity from local
    token counts, not global), dispatched expert-major by one all_to_all,
    transformed where their expert's weights live, and returned by a second
    all_to_all.

    vs the GSPMD baseline (``moe_block``): the baseline's routing tensors are
    data-dependent gathers over the *global* token axis, which GSPMD cannot
    shard — every device materializes and multiplies the full [E, C_global,
    D] expert batch.  Here per-device dispatch work is T/(DP·MP)·k·cf rows —
    proportional to *active* parameters (measured in EXPERIMENTS.md §Perf).

    x enters sharded [batch→dp, seq→model]; the surrounding attention blocks
    re-gather the sequence axis as needed (GSPMD inserts the collectives).
    """
    from jax.sharding import PartitionSpec as P

    E, K = cfg.moe_experts, cfg.moe_topk
    mp = mesh.shape[model_axis]
    assert E % mp == 0, (E, mp)

    def body(w1, w3, w2, router, norm_w, xs):
        b, s, D = xs.shape
        T = b * s
        C = _capacity(cfg, T)
        h = rms_norm(xs, norm_w, cfg.norm_eps).reshape(T, D)
        idx, weights, _ = _route(cfg, h, router)

        onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)
        flat = onehot.reshape(T * K, E)
        pos_in_e = jnp.cumsum(flat, axis=0) - flat
        pos = (pos_in_e * flat).sum(-1).reshape(T, K)
        keep = pos < C
        slot_e = idx.reshape(-1)
        slot_c = jnp.where(keep.reshape(-1), pos.reshape(-1), C)
        tok = jnp.repeat(jnp.arange(T), K)
        gather_idx = jnp.zeros((E, C + 1), jnp.int32).at[slot_e, slot_c].set(
            tok.astype(jnp.int32), mode="drop")[:, :C]
        slot_used = jnp.zeros((E, C + 1), jnp.bool_).at[slot_e, slot_c].set(
            keep.reshape(-1), mode="drop")[:, :C]
        w_slot = jnp.zeros((E, C + 1), jnp.float32).at[slot_e, slot_c].set(
            weights.reshape(-1), mode="drop")[:, :C]

        xe = h[gather_idx] * slot_used[..., None].astype(h.dtype)  # [E, C, D]
        # dispatch: expert-major exchange (RAF: compute where the params live)
        xe = jax.lax.all_to_all(xe, model_axis, split_axis=0, concat_axis=1,
                                tiled=True)  # [E/mp, C·mp, D]
        act = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, w1)) * jnp.einsum(
            "ecd,edf->ecf", xe, w3)
        ye = jnp.einsum("ecf,efd->ecd", act, w2)  # [E/mp, C·mp, D]
        # return partial results to the token owners
        ye = jax.lax.all_to_all(ye, model_axis, split_axis=1, concat_axis=0,
                                tiled=True)  # [E, C, D]
        contrib = ye * w_slot[..., None].astype(ye.dtype)
        out = jnp.zeros((T, D), ye.dtype).at[gather_idx.reshape(-1)].add(
            contrib.reshape(E * C, D))
        return xs + out.reshape(b, s, D)

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(model_axis, None, None),  # w1 [E, D, F] — expert-sharded
            P(model_axis, None, None),  # w3
            P(model_axis, None, None),  # w2
            P(None, None),  # router (replicated)
            P(None),  # norm
            P(dp_axes, model_axis, None),  # x: batch→dp, seq→model
        ),
        out_specs=P(dp_axes, model_axis, None),
        check_vma=False,
    )(p["w1"], p["w3"], p["w2"], p["router"], p["norm"], x)


def router_stats(cfg: ArchConfig, p: Dict, x: jnp.ndarray) -> Dict:
    b, s, D = x.shape
    h = rms_norm(x, p["norm"], cfg.norm_eps).reshape(b * s, D)
    idx, w, probs = _route(cfg, h, p["router"])
    counts = jnp.zeros(cfg.moe_experts).at[idx.reshape(-1)].add(1.0)
    return {"expert_load": counts / counts.sum(), "entropy": -(probs * jnp.log(probs + 1e-9)).sum(-1).mean()}
