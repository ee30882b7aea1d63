"""Pallas TPU kernel: scalar-prefetch row gather (feature/cache fetch).

Heta's cache fetch path is a batched gather of feature rows by node id
(paper §6).  On TPU the idiomatic shape is a *scalar-prefetched* grid: the
index vector is available to the BlockSpec ``index_map`` before the kernel
body runs, so each grid step's DMA engine pulls exactly the [rows_per_step,
d] slice of the HBM-resident table that the step needs — the gather happens
in the DMA schedule, not in compute.

Grid: (n_steps,) — step i copies ``table[idx[i]]`` into ``out[i]``.  With
rows ≥ lane width this saturates HBM bandwidth; the miss-penalty *fixed
overhead* the paper measures (Fig. 7a) corresponds to the per-DMA setup
cost, which is why small-dim node types have larger o_a.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["gather_rows_pallas"]


def _kernel(idx_ref, tab_ref, out_ref):
    out_ref[...] = tab_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_rows_pallas(
    table: jnp.ndarray,  # [num_rows, d]
    idx: jnp.ndarray,  # [n] int32
    interpret: bool = True,
) -> jnp.ndarray:
    n = idx.shape[0]
    num_rows, d = table.shape
    # rows travel as [1, 1, d] blocks of a [rows, 1, d] view: a block's last
    # two dims must be (8k, 128k) or the array's own, and (1, d) is the
    # array's own only with the unit axis in the middle
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[pl.BlockSpec((1, 1, d), lambda i, idx_ref: (idx_ref[i], 0, 0))],
        out_specs=pl.BlockSpec((1, 1, d), lambda i, idx_ref: (i, 0, 0)),
    )
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, 1, d), table.dtype),
        name="gather_rows_pallas",
        interpret=interpret,
    )(idx.astype(jnp.int32), table.reshape(num_rows, 1, d))
    return out.reshape(n, d)
