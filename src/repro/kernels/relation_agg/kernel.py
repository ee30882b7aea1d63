"""Pallas TPU kernel: fused masked-mean neighbor aggregation + projection.

The R-GCN relation-specific aggregation (paper Eq. 1) is the compute hot
spot of Heta's per-partition work.  A naive implementation materializes the
masked-mean intermediate [n, d_in] in HBM and then runs a separate matmul;
this kernel keeps the mean in VMEM and feeds the MXU directly:

  grid (i, o, c) over (target blocks, d_out blocks, d_in chunks)

  * the [bn, f, bc] neighbor block is reduced over f on the VPU,
  * the [bn, bc] mean tile multiplies the [bc, bo] weight tile on the MXU,
  * partials accumulate in a float32 VMEM scratch across the c dimension.

Block shapes default to MXU-aligned 128 multiples; the f axis stays whole
(fanouts are small: 4–25) so the reduction never crosses blocks.

HBM→VMEM traffic: h is read once (n·f·d_in), w once per target block,
out written once — the naive two-pass adds a full [n, d_in] HBM write +
read for the intermediate.  VMEM working set per step:
bn·f·bc + bn·f + bc·bo + bn·bo floats ≈ 128·25·512·4B ≈ 6.5 MB < 16 MB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["relation_agg_pallas"]


def _kernel(h_ref, mask_ref, w_ref, b_ref, out_ref, acc_ref, *, n_chunks: int):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    h = h_ref[...]  # [bn, f, bc]
    m = mask_ref[...].astype(h.dtype)  # [bn, f]
    # Σ_f mask·h as a batched (bn) [1,f]x[f,bc] contraction on the MXU/VPU
    s = jax.lax.dot_general(
        m[:, None, :], h, (((2,), (1,)), ((0,), (0,)))
    )[:, 0, :]  # [bn, bc]
    cnt = jnp.maximum(jnp.sum(m, axis=-1, keepdims=True), 1.0)
    mean = s / cnt
    acc_ref[...] += jax.lax.dot(
        mean.astype(w_ref.dtype), w_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(c == n_chunks - 1)
    def _done():
        out_ref[...] = (
            acc_ref[...] + b_ref[...].astype(jnp.float32)[None, :]
        ).astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_out", "block_in", "interpret")
)
def relation_agg_pallas(
    h: jnp.ndarray,  # [n, f, d_in]
    mask: jnp.ndarray,  # [n, f]
    w: jnp.ndarray,  # [d_in, d_out]
    b: jnp.ndarray,  # [d_out]
    block_n: int = 128,
    block_out: int = 128,
    block_in: int = 512,
    interpret: bool = True,
) -> jnp.ndarray:
    n, f, d_in = h.shape
    d_out = w.shape[1]
    bn = min(block_n, n)
    bo = min(block_out, d_out)
    bc = min(block_in, d_in)
    grid = (pl.cdiv(n, bn), pl.cdiv(d_out, bo), pl.cdiv(d_in, bc))

    return pl.pallas_call(
        functools.partial(_kernel, n_chunks=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, f, bc), lambda i, o, c: (i, 0, c)),
            pl.BlockSpec((bn, f), lambda i, o, c: (i, 0)),
            pl.BlockSpec((bc, bo), lambda i, o, c: (c, o)),
            pl.BlockSpec((bo,), lambda i, o, c: (o,)),
        ],
        out_specs=pl.BlockSpec((bn, bo), lambda i, o, c: (i, o)),
        out_shape=jax.ShapeDtypeStruct((n, d_out), h.dtype),
        scratch_shapes=[pltpu.VMEM((bn, bo), jnp.float32)],
        name="relation_agg_pallas",
        interpret=interpret,
    )(h, mask, w, b)
