"""Pallas TPU kernels: stacked relation aggregation for all branch slots.

One ``pallas_call`` runs a whole level of the SPMD executor — the grid's
leading dimension is the shard's branch-slot axis, and the per-slot scope
indices (``LevelPlan.slot_u``) ride in as **scalar-prefetch** operands.
Each grid step's ``index_map`` therefore reads its weight block *directly
from the ``[U, ...]`` stack in HBM*: a parameter shared by many slots is
DMA'd once per slot-step straight out of the single stacked copy — never
materialized as a gathered ``[rb, ...]`` duplicate in HBM, which is what
the gather-then-vmap path pays every step ("Characterizing and
Understanding HGNN Training on GPUs" finds exactly this redundant parameter
movement dominating HGNN kernels; HiHGNN builds on the same reusability).

Three kernels:

  * :func:`stacked_mean_linear_pallas` — the rgcn-family AGG_r: masked-mean
    over the fanout fused with the output projection.  Grid (slot, node
    block, d_out block, d_in chunk); float32 VMEM accumulator across d_in
    chunks; mean is never written to HBM.
  * :func:`stacked_mean_linear_dh_pallas` — the hand-written backward for
    the neighbor activations: ``dh = (g @ w[slot]ᵀ) · mask / cnt``, again
    reading weight blocks via scalar prefetch (no gathered ``wᵀ`` copies).
  * :func:`stacked_softmax_combine_pallas` — the attention-family epilogue
    (rgat/hgt): masked softmax over the fanout fused with the head-wise
    weighted combine, so attention probabilities never round-trip to HBM.
    Logit/value projections stay outside (they carry the module-specific
    einsums and remain under XLA autodiff).  Kept as the ``attn_parts``
    oracle path; superseded on the hot path by the kernel below.
  * :func:`stacked_attn_epilogue_pallas` — the *fully fused* attention
    AGG_r (DESIGN.md §8): the per-slot logit/value projections now stream
    from the ``[U, d_in, nh*dh]`` stacks via the same scalar-prefetch
    indirection, accumulate across d_in chunks in float32 VMEM scratch,
    and feed the masked softmax + combine epilogue in the same grid step —
    neither the projected logits/values *nor* a gathered weight copy ever
    round-trips through HBM on the forward.  Optional per-slot
    ``[nh, dh, dh]`` transforms (HGT's ``w_att``/``w_msg``) apply in the
    epilogue, as block-diagonal ``[H, H]`` matrices.  With
    ``with_residuals`` the pre-transform projections are written out once
    for the backward.
  * :func:`stacked_attn_dh_pallas` — the backward w.r.t. the neighbor
    activations: ``dh = dz @ we[slot]ᵀ (+ dv @ wv[slot]ᵀ)``, weight blocks
    again read via scalar prefetch.

All shapes arrive pre-padded to block multiples (``ops.py`` owns padding
and slicing); fanout ``f`` stays whole — sampled fanouts are 3–25, so the
reduction never crosses blocks.  Inside the kernels every array keeps
``H = nh·dh`` (or a d-chunk) on the lane axis: per-head quantities are
*head-expanded* to all ``dh`` lanes of their head, and per-head sums are a
matmul with a 0/1 head-sum matrix.  Mosaic lowers neither an einsum that
keeps a batch dimension nor a reshape that splits the lane axis into heads.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "stacked_mean_linear_pallas",
    "stacked_mean_linear_dh_pallas",
    "stacked_softmax_combine_pallas",
    "stacked_attn_epilogue_pallas",
    "stacked_attn_dh_pallas",
]


# --------------------------------------------------------------------------
# masked-mean + projection (rgcn family), forward
# --------------------------------------------------------------------------


def _mean_linear_kernel(u_ref, h_ref, m_ref, w_ref, b_ref, out_ref, acc_ref,
                        *, n_chunks: int):
    c = pl.program_id(3)

    @pl.when(c == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    h = h_ref[0]  # [bn, f, bc]
    m = m_ref[0].astype(h.dtype)  # [bn, f]
    # identical formulation to relmod.masked_mean (a masked sum over the
    # fanout, not a contraction: Mosaic lowers no dot whose rhs keeps a
    # batch dim), so the interpret-mode forward is bit-equal to the oracle
    s = jnp.sum(h * m[:, :, None], axis=1)
    cnt = jnp.maximum(jnp.sum(m, axis=-1, keepdims=True), 1.0)
    mean = s / cnt
    acc_ref[...] += jax.lax.dot(
        mean.astype(w_ref.dtype), w_ref[0], preferred_element_type=jnp.float32
    )

    @pl.when(c == n_chunks - 1)
    def _done():
        out_ref[0] = (
            acc_ref[...] + b_ref[0].astype(jnp.float32)
        ).astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_out", "block_in", "interpret")
)
def stacked_mean_linear_pallas(
    h: jnp.ndarray,  # [rb, n, f, d_in]   (n, d_in pre-padded to blocks)
    mask: jnp.ndarray,  # [rb, n, f]
    w: jnp.ndarray,  # [U, d_in, d_out]
    b: jnp.ndarray,  # [U, 1, d_out]  (unit axis: a (1, bo) block is tile-legal)
    slot_u: jnp.ndarray,  # [rb] int32 — slot -> stack row (scalar prefetch)
    block_n: int = 128,
    block_out: int = 128,
    block_in: int = 512,
    interpret: bool = True,
) -> jnp.ndarray:
    rb, n, f, d_in = h.shape
    d_out = w.shape[2]
    bn, bo, bc = block_n, block_out, block_in
    grid = (rb, pl.cdiv(n, bn), pl.cdiv(d_out, bo), pl.cdiv(d_in, bc))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bn, f, bc), lambda s, i, o, c, u: (s, i, 0, c)),
            pl.BlockSpec((1, bn, f), lambda s, i, o, c, u: (s, i, 0)),
            pl.BlockSpec((1, bc, bo), lambda s, i, o, c, u: (u[s], c, o)),
            pl.BlockSpec((1, 1, bo), lambda s, i, o, c, u: (u[s], 0, o)),
        ],
        out_specs=pl.BlockSpec((1, bn, bo), lambda s, i, o, c, u: (s, i, o)),
        scratch_shapes=[pltpu.VMEM((bn, bo), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_mean_linear_kernel, n_chunks=grid[3]),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rb, n, d_out), h.dtype),
        name="stacked_mean_linear_pallas",
        interpret=interpret,
    )(slot_u.astype(jnp.int32), h, mask, w, b)


# --------------------------------------------------------------------------
# masked-mean + projection, backward w.r.t. the neighbor activations
# --------------------------------------------------------------------------


def _mean_linear_dh_kernel(u_ref, g_ref, m_ref, w_ref, dh_ref, acc_ref,
                           *, n_chunks: int):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    g = g_ref[0]  # [bn, bk]
    w = w_ref[0]  # [bc, bk]
    # dmean partial: g @ w^T accumulated over d_out chunks
    acc_ref[...] += jax.lax.dot_general(
        g.astype(w.dtype), w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == n_chunks - 1)
    def _done():
        m = m_ref[0].astype(jnp.float32)  # [bn, f]
        cnt = jnp.maximum(jnp.sum(m, axis=-1, keepdims=True), 1.0)
        dmean = acc_ref[...] / cnt  # [bn, bc]
        dh_ref[0] = (dmean[:, None, :] * m[:, :, None]).astype(dh_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_out", "block_in", "interpret")
)
def stacked_mean_linear_dh_pallas(
    g: jnp.ndarray,  # [rb, n, d_out]
    mask: jnp.ndarray,  # [rb, n, f]
    w: jnp.ndarray,  # [U, d_in, d_out]
    slot_u: jnp.ndarray,  # [rb] int32
    block_n: int = 128,
    block_out: int = 128,
    block_in: int = 512,
    interpret: bool = True,
) -> jnp.ndarray:
    rb, n, d_out = g.shape
    f = mask.shape[2]
    d_in = w.shape[1]
    bn, bo, bc = block_n, block_out, block_in
    grid = (rb, pl.cdiv(n, bn), pl.cdiv(d_in, bc), pl.cdiv(d_out, bo))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bn, bo), lambda s, i, c, k, u: (s, i, k)),
            pl.BlockSpec((1, bn, f), lambda s, i, c, k, u: (s, i, 0)),
            pl.BlockSpec((1, bc, bo), lambda s, i, c, k, u: (u[s], c, k)),
        ],
        out_specs=pl.BlockSpec((1, bn, f, bc), lambda s, i, c, k, u: (s, i, 0, c)),
        scratch_shapes=[pltpu.VMEM((bn, bc), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_mean_linear_dh_kernel, n_chunks=grid[3]),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rb, n, f, d_in), g.dtype),
        name="stacked_mean_linear_dh_pallas",
        interpret=interpret,
    )(slot_u.astype(jnp.int32), g, mask, w)


# --------------------------------------------------------------------------
# masked softmax + head-wise combine (rgat/hgt epilogue)
# --------------------------------------------------------------------------


def _masked_softmax_combine(e, m, v):
    """Masked softmax over the fanout axis + weighted sum of ``v``.

    ``e`` and ``v`` are ``[bn, f, H]`` with the logits *head-expanded*: lane
    ``(h, d)`` of ``e`` holds head ``h``'s logit, so the softmax runs per
    lane and the combine is an elementwise product — no ``[.., nh, dh]``
    reshape, which Mosaic cannot lay out.  Numerics per lane are those of
    ``relmod.masked_softmax``."""
    mm = m.astype(e.dtype)[:, :, None]  # [bn, f, 1]; Mosaic reshapes no i1
    neg = jnp.asarray(jnp.finfo(e.dtype).min, e.dtype)
    em = jnp.where(mm > 0, e, neg)
    em = em - jnp.max(em, axis=1, keepdims=True)
    z = jnp.exp(em) * mm
    alpha = z / jnp.maximum(jnp.sum(z, axis=1, keepdims=True), 1e-9)
    return jnp.sum(alpha * v.astype(alpha.dtype), axis=1)


def _softmax_combine_kernel(e_ref, m_ref, v_ref, out_ref):
    out_ref[0] = _masked_softmax_combine(
        e_ref[0], m_ref[0], v_ref[0]).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def stacked_softmax_combine_pallas(
    e: jnp.ndarray,  # [rb, n, f, nh*dh]  logits head-expanded to H lanes
    mask: jnp.ndarray,  # [rb, n, f]
    v: jnp.ndarray,  # [rb, n, f, nh*dh]
    block_n: int = 128,
    interpret: bool = True,
) -> jnp.ndarray:
    rb, n, f, H = v.shape
    bn = block_n
    grid = (rb, pl.cdiv(n, bn))
    return pl.pallas_call(
        _softmax_combine_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bn, f, H), lambda s, i: (s, i, 0, 0)),
            pl.BlockSpec((1, bn, f), lambda s, i: (s, i, 0)),
            pl.BlockSpec((1, bn, f, H), lambda s, i: (s, i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bn, H), lambda s, i: (s, i, 0)),
        out_shape=jax.ShapeDtypeStruct((rb, n, H), e.dtype),
        name="stacked_softmax_combine_pallas",
        interpret=interpret,
    )(e, mask, v)


# --------------------------------------------------------------------------
# fully fused attention AGG_r: stack-streamed projections + softmax+combine
# --------------------------------------------------------------------------


_EPILOGUE_VMEM_LIMIT = 64 * 2**20


def _lane_matmul(x, w):
    """``[bn, f, H] @ [H, K]`` over the lane axis, float32 at full precision."""
    bn, f, H = x.shape
    y = jax.lax.dot(x.reshape(bn * f, H), w.astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32)
    return y.reshape(bn, f, w.shape[1])


def _head_sum_matrix(nh: int, dh: int):
    """``[H, H]`` 0/1 matrix summing each head's ``dh`` lanes and writing the
    sum back to all of them (``kron(I_nh, ones(dh, dh))``)."""
    H = nh * dh
    row = jax.lax.broadcasted_iota(jnp.int32, (H, H), 0) // dh
    col = jax.lax.broadcasted_iota(jnp.int32, (H, H), 1) // dh
    return (row == col).astype(jnp.float32)


def _attn_epilogue_kernel(u_ref, *refs, n_chunks, num_heads, head_dim, scale,
                          slope, has_eb, has_post, shared_v, with_res):
    nh, dh = num_heads, head_dim
    it = iter(refs)
    h_ref, m_ref, qv_ref = next(it), next(it), next(it)
    eb_ref = next(it) if has_eb else None
    we_ref = next(it)
    wv_ref = None if shared_v else next(it)
    pe_ref = next(it) if has_post else None
    pv_ref = next(it) if has_post else None
    out_ref = next(it)
    z_ref = next(it) if with_res else None
    v_ref = next(it) if (with_res and not shared_v) else None
    acc_z = next(it)
    acc_v = None if shared_v else next(it)

    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        acc_z[...] = jnp.zeros_like(acc_z)
        if acc_v is not None:
            acc_v[...] = jnp.zeros_like(acc_v)

    h = h_ref[0]  # [bn, f, bc]
    bn, f, bc = h.shape
    hf = h.reshape(bn * f, bc)
    acc_z[...] += jax.lax.dot(
        hf.astype(we_ref.dtype), we_ref[0], preferred_element_type=jnp.float32
    ).reshape(bn, f, nh * dh)
    if acc_v is not None:
        acc_v[...] += jax.lax.dot(
            hf.astype(wv_ref.dtype), wv_ref[0],
            preferred_element_type=jnp.float32,
        ).reshape(bn, f, nh * dh)

    @pl.when(c == n_chunks - 1)
    def _done():
        z0 = acc_z[...]  # [bn, f, nh*dh] float32
        v0 = z0 if acc_v is None else acc_v[...]
        if has_post:
            # per-head [dh, dh] transforms as one block-diagonal [H, H]
            zt = _lane_matmul(z0, pe_ref[0])
            vt = _lane_matmul(v0, pv_ref[0])
        else:
            zt, vt = z0, v0
        qv = qv_ref[0].astype(jnp.float32)  # [bn, H]
        # per-head logit sum, broadcast back over the head's dh lanes
        e = _lane_matmul(zt * qv[:, None, :], _head_sum_matrix(nh, dh)) * scale
        if has_eb:
            e = e + eb_ref[0].astype(jnp.float32)[:, None, :]
        if slope is not None:
            e = jax.nn.leaky_relu(e, negative_slope=slope)
        out = _masked_softmax_combine(e, m_ref[0], vt)
        out_ref[0] = out.astype(out_ref.dtype)
        if z_ref is not None:
            z_ref[0] = z0.astype(z_ref.dtype)
        if v_ref is not None:
            v_ref[0] = v0.astype(v_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("num_heads", "head_dim", "scale", "slope", "with_residuals",
                     "block_n", "block_in", "interpret"),
)
def stacked_attn_epilogue_pallas(
    h: jnp.ndarray,  # [rb, n, f, d_in]  (n, d_in pre-padded to blocks)
    mask: jnp.ndarray,  # [rb, n, f]
    qv: jnp.ndarray,  # [rb, n, nh*dh]
    eb,  # [rb, n, nh*dh] head-expanded additive logits, or None
    we: jnp.ndarray,  # [Ue, d_in, nh*dh]
    wv,  # [Uv, d_in, nh*dh] or None (shares we)
    pe,  # [Ua, nh*dh, nh*dh] block-diagonal logits transform, or None
    pv,  # [Ua, nh*dh, nh*dh] block-diagonal values transform, or None
    us: jnp.ndarray,  # [3, rb] int32 — rows (ue, uv, ua) (scalar prefetch)
    num_heads: int,
    head_dim: int,
    scale: float = 1.0,
    slope=None,
    with_residuals: bool = False,
    block_n: int = 128,
    block_in: int = 512,
    interpret: bool = True,
):
    rb, n, f, d_in = h.shape
    nh, dh = num_heads, head_dim
    H = nh * dh
    bn, bc = block_n, block_in
    has_eb, has_post, shared_v = eb is not None, pe is not None, wv is None
    grid = (rb, pl.cdiv(n, bn), pl.cdiv(d_in, bc))

    in_specs = [
        pl.BlockSpec((1, bn, f, bc), lambda s, i, c, u: (s, i, 0, c)),
        pl.BlockSpec((1, bn, f), lambda s, i, c, u: (s, i, 0)),
        pl.BlockSpec((1, bn, H), lambda s, i, c, u: (s, i, 0)),
    ]
    operands = [h, mask, qv]
    if has_eb:
        in_specs.append(pl.BlockSpec((1, bn, H), lambda s, i, c, u: (s, i, 0)))
        operands.append(eb)
    in_specs.append(
        pl.BlockSpec((1, bc, H), lambda s, i, c, u: (u[0, s], c, 0)))
    operands.append(we)
    if not shared_v:
        in_specs.append(
            pl.BlockSpec((1, bc, H), lambda s, i, c, u: (u[1, s], c, 0)))
        operands.append(wv)
    if has_post:
        in_specs.append(
            pl.BlockSpec((1, H, H), lambda s, i, c, u: (u[2, s], 0, 0)))
        in_specs.append(
            pl.BlockSpec((1, H, H), lambda s, i, c, u: (u[2, s], 0, 0)))
        operands.extend([pe, pv])

    out_specs = [pl.BlockSpec((1, bn, H), lambda s, i, c, u: (s, i, 0))]
    out_shape = [jax.ShapeDtypeStruct((rb, n, H), h.dtype)]
    if with_residuals:
        out_specs.append(
            pl.BlockSpec((1, bn, f, H), lambda s, i, c, u: (s, i, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((rb, n, f, H), h.dtype))
        if not shared_v:
            out_specs.append(
                pl.BlockSpec((1, bn, f, H), lambda s, i, c, u: (s, i, 0, 0)))
            out_shape.append(jax.ShapeDtypeStruct((rb, n, f, H), h.dtype))

    scratch = [pltpu.VMEM((bn, f, H), jnp.float32)]
    if not shared_v:
        scratch.append(pltpu.VMEM((bn, f, H), jnp.float32))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        functools.partial(
            _attn_epilogue_kernel, n_chunks=grid[2], num_heads=nh, head_dim=dh,
            scale=scale, slope=slope, has_eb=has_eb, has_post=has_post,
            shared_v=shared_v, with_res=with_residuals,
        ),
        grid_spec=grid_spec,
        out_shape=out_shape,
        # the [bn, f, H] float32 accumulators and residual tiles (lane-padded
        # to 128) overflow the 16 MiB default scoped VMEM once HGT carries
        # separate K and V projections; v5e has 128 MiB per core
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_EPILOGUE_VMEM_LIMIT),
        name="stacked_attn_epilogue_pallas",
        interpret=interpret,
    )(us.astype(jnp.int32), *operands)
    return out if with_residuals else out[0]


# --------------------------------------------------------------------------
# fused attention backward w.r.t. the neighbor activations
# --------------------------------------------------------------------------


def _attn_dh_kernel(u_ref, *refs, shared_v):
    it = iter(refs)
    dz_ref = next(it)
    dv_ref = None if shared_v else next(it)
    we_ref = next(it)
    wv_ref = None if shared_v else next(it)
    dh_ref = next(it)

    dz = dz_ref[0]  # [bn, f, H]
    bn, f, H = dz.shape
    we = we_ref[0]  # [bc, H]
    acc = jax.lax.dot_general(
        dz.reshape(bn * f, H).astype(we.dtype), we, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if dv_ref is not None:
        wv = wv_ref[0]
        acc += jax.lax.dot_general(
            dv_ref[0].reshape(bn * f, H).astype(wv.dtype), wv,
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        )
    dh_ref[0] = acc.reshape(bn, f, -1).astype(dh_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_in", "interpret")
)
def stacked_attn_dh_pallas(
    dz: jnp.ndarray,  # [rb, n, f, H]
    dv,  # [rb, n, f, H] or None (shared projection)
    we: jnp.ndarray,  # [Ue, d_in, H]
    wv,  # [Uv, d_in, H] or None
    us: jnp.ndarray,  # [3, rb] int32
    block_n: int = 128,
    block_in: int = 512,
    interpret: bool = True,
) -> jnp.ndarray:
    rb, n, f, H = dz.shape
    d_in = we.shape[1]
    bn, bc = block_n, block_in
    shared_v = dv is None
    grid = (rb, pl.cdiv(n, bn), pl.cdiv(d_in, bc))
    in_specs = [pl.BlockSpec((1, bn, f, H), lambda s, i, c, u: (s, i, 0, 0))]
    operands = [dz]
    if not shared_v:
        in_specs.append(
            pl.BlockSpec((1, bn, f, H), lambda s, i, c, u: (s, i, 0, 0)))
        operands.append(dv)
    in_specs.append(
        pl.BlockSpec((1, bc, H), lambda s, i, c, u: (u[0, s], c, 0)))
    operands.append(we)
    if not shared_v:
        in_specs.append(
            pl.BlockSpec((1, bc, H), lambda s, i, c, u: (u[1, s], c, 0)))
        operands.append(wv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bn, f, bc), lambda s, i, c, u: (s, i, 0, c)),
    )
    return pl.pallas_call(
        functools.partial(_attn_dh_kernel, shared_v=shared_v),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rb, n, f, d_in), dz.dtype),
        name="stacked_attn_dh_pallas",
        interpret=interpret,
    )(us.astype(jnp.int32), *operands)
