"""Pallas TPU flash attention (forward): online-softmax blocked attention.

Grid (bh, iq, jk), jk innermost ("arbitrary" — sequential revisit of the
output block).  Per step the [bq, d] query tile attends to a [bk, d]
key/value tile; running max/denominator live in VMEM scratch, so the
[sq, sk] score matrix never exists in HBM — the point of flash attention,
and on TPU the tiles feed the MXU at 128-alignment.

Causal and sliding-window structure is exploited by *skipping whole k
blocks* (pl.when) — for window attention the visited diagonal band makes
compute O(sq·window) instead of O(sq·sk), which is what lets the dense
architectures run the 500k-token decode shape (DESIGN.md §4).

VMEM per step: bq·d + 2·bk·d + bq·bk + 2·bq·128 floats ≈
(128·128 + 2·128·128 + 128·128 + 2·128·128)·4B ≈ 0.4 MB — deep in budget,
so ops.py can raise bq/bk to 256/512 for long sequences.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_pallas"]

NEG_INF = -1e30
LANES = 128


def _kernel(
    q_ref, k_ref, v_ref, out_ref, acc_ref, m_ref, l_ref,
    *, scale: float, causal: bool, window: Optional[int], q_offset: int,
    bq: int, bk: int, n_k: int,
):
    jk = pl.program_id(2)
    iq = pl.program_id(1)

    @pl.when(jk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # block-level structure: skip k blocks entirely outside the band
    q_lo = iq * bq + q_offset  # global position of the block's first query
    q_hi = q_lo + bq - 1
    k_lo = jk * bk
    k_hi = k_lo + bk - 1
    live = True
    if causal:
        live = k_lo <= q_hi
    if window is not None:
        live = jnp.logical_and(live, k_hi > q_lo - window)

    @pl.when(live if not isinstance(live, bool) else True)
    def _compute():
        q = q_ref[0]  # [bq, d]
        k = k_ref[0]  # [bk, d]
        v = v_ref[0]  # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [bq, bk]
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]  # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)  # masked slots: exp(NEG_INF - m) == 0
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(jk == n_k - 1)
    def _done():
        out_ref[0] = (
            acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-20)
        ).astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "q_offset", "block_q", "block_k", "interpret"),
)
def flash_attention_pallas(
    q: jnp.ndarray,  # [bh, sq, d]
    k: jnp.ndarray,  # [bh, sk, d]
    v: jnp.ndarray,  # [bh, sk, d]
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = True,
) -> jnp.ndarray:
    bh, sq, d = q.shape
    sk = k.shape[1]
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    scale = 1.0 / float(np.sqrt(d))
    grid = (bh, pl.cdiv(sq, bq), pl.cdiv(sk, bk))
    return pl.pallas_call(
        functools.partial(
            _kernel,
            scale=scale, causal=causal, window=window, q_offset=q_offset,
            bq=bq, bk=bk, n_k=grid[2],
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q, k, v)
