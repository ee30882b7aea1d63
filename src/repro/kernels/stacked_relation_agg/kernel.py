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

The kernels:

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
    round-trips through HBM.  Optional per-slot ``[nh, dh, dh]`` transforms
    (HGT's ``w_att``/``w_msg``), as block-diagonal ``[H, H]`` matrices, act
    once per destination row: on the query before the logits and on the
    combined values after the softmax.
  * :func:`stacked_attn_bwd_pallas` — its backward: per (slot, node block)
    it recomputes the block's projections, logits and softmax in VMEM and
    writes the query (and additive-logit) gradients, where they are needed
    the neighbor-row gradients, and accumulates the slot's weight gradients
    across its node blocks.  Nothing with a row per edge slot is saved by
    the forward or built in HBM by the backward.

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
    "stacked_attn_bwd_pallas",
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


def _masked_softmax(e, m):
    """Masked softmax over the fanout axis of ``[bn, f, H]`` logits that are
    *head-expanded* — lane ``(h, d)`` of ``e`` holds head ``h``'s logit, so
    the softmax runs per lane with no ``[.., nh, dh]`` reshape, which Mosaic
    cannot lay out.  Numerics per lane are those of
    ``relmod.masked_softmax``; an all-masked row gives zeros."""
    mm = m.astype(e.dtype)[:, :, None]  # [bn, f, 1]; Mosaic reshapes no i1
    neg = jnp.asarray(jnp.finfo(e.dtype).min, e.dtype)
    em = jnp.where(mm > 0, e, neg)
    em = em - jnp.max(em, axis=1, keepdims=True)
    z = jnp.exp(em) * mm
    return z / jnp.maximum(jnp.sum(z, axis=1, keepdims=True), 1e-9)


def _masked_softmax_combine(e, m, v):
    """:func:`_masked_softmax` + the weighted sum of ``v`` ``[bn, f, H]``."""
    alpha = _masked_softmax(e, m)
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


def _rows_matmul(x, w, transpose_w: bool = False):
    """``[m, H] @ w`` (or ``@ wᵀ``), float32 at full precision: the per-head
    transforms, applied once per destination row."""
    dims = (((1,), (1,)) if transpose_w else ((1,), (0,)), ((), ()))
    return jax.lax.dot_general(x, w.astype(jnp.float32), dims,
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _rows_outer(a, b, precision=jax.lax.Precision.HIGHEST):
    """``aᵀ @ b`` over the rows of ``[m, K]`` and ``[m, N]``, float32."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               precision=precision,
                               preferred_element_type=jnp.float32)


def _head_group(nh: int, dh: int) -> int:
    """Lane width of one head-sum block: whole 128-lane groups where the
    heads tile them, else all of ``H``."""
    H = nh * dh
    return 128 if H % 128 == 0 and 128 % dh == 0 else H


def _head_sum(x, nh: int, dh: int):
    """``[m, H]`` -> each head's sum over its ``dh`` lanes, written back to
    all of them: a matmul with the 0/1 matrix ``kron(I, ones(dh, dh))`` per
    group of lanes (a block-diagonal sum, so a group of 128 lanes needs only
    its own 128x128 block), float32 at full precision."""
    G = _head_group(nh, dh)
    row = jax.lax.broadcasted_iota(jnp.int32, (G, G), 0) // dh
    col = jax.lax.broadcasted_iota(jnp.int32, (G, G), 1) // dh
    s = (row == col).astype(jnp.float32)
    parts = [jax.lax.dot(x[:, g:g + G], s, precision=jax.lax.Precision.HIGHEST,
                         preferred_element_type=jnp.float32)
             for g in range(0, x.shape[1], G)]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def _attn_probs(z0, qk, m, eb, *, nh, dh, scale, slope):
    """Logits and masked softmax of one block, all ``[bn, f, H]`` float32
    with per-head values head-expanded: ``e0`` before the leaky ReLU and
    ``alpha``.  ``qk`` ``[bn, H]`` is the query with the logits transform
    already applied (``qv @ W_ATTᵀ``), so no per-edge transform is needed:
    ``(z0 @ W_ATT) . qv == z0 . (qv @ W_ATTᵀ)`` head by head."""
    bn, f, H = z0.shape
    e0 = _head_sum((z0 * qk[:, None, :]).reshape(bn * f, H), nh, dh)
    e0 = e0.reshape(bn, f, H) * scale
    if eb is not None:
        e0 = e0 + eb[:, None, :]
    e = e0 if slope is None else jax.nn.leaky_relu(e0, negative_slope=slope)
    return e0, _masked_softmax(e, m)


def _attn_epilogue_kernel(u_ref, *refs, n_chunks, num_heads, head_dim, scale,
                          slope, has_eb, has_post, shared_v):
    nh, dh = num_heads, head_dim
    it = iter(refs)
    h_ref, m_ref, qv_ref = next(it), next(it), next(it)
    eb_ref = next(it) if has_eb else None
    we_ref = next(it)
    wv_ref = None if shared_v else next(it)
    pe_ref = next(it) if has_post else None
    pv_ref = next(it) if has_post else None
    out_ref = next(it)
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
        qv = qv_ref[0].astype(jnp.float32)  # [bn, H]
        # the per-head [dh, dh] transforms, as block-diagonal [H, H]
        # matrices, act per destination row: on the query before the logits
        # and on the combined values after the softmax
        qk = _rows_matmul(qv, pe_ref[0], transpose_w=True) if has_post else qv
        eb = eb_ref[0].astype(jnp.float32) if has_eb else None
        _, alpha = _attn_probs(z0, qk, m_ref[0], eb, nh=nh, dh=dh,
                               scale=scale, slope=slope)
        ov = jnp.sum(alpha * v0, axis=1)  # [bn, H]
        out = _rows_matmul(ov, pv_ref[0]) if has_post else ov
        out_ref[0] = out.astype(out_ref.dtype)


def _attn_in_specs(bn, f, d_blk, H, has_eb, shared_v, has_post, *, chunked,
                   with_g=False):
    """Block specs of the attention kernels' inputs, in their order: the
    neighbor rows, the mask, the queries (and the output's cotangent), the
    additive logits, the projection stacks and the per-head transforms, each
    weight block read from its stack row by scalar prefetch.  The forward's
    grid is (slot, node block, d_in chunk) (``chunked``), the backward's
    (slot, node block) with ``d_in`` whole."""
    if chunked:
        rows = lambda s, i, c, u: (s, i, 0, c)
        row = lambda s, i, c, u: (s, i, 0)
        weight = lambda r: lambda s, i, c, u: (u[r, s], c, 0)
    else:
        rows = lambda s, i, u: (s, i, 0, 0)
        row = lambda s, i, u: (s, i, 0)
        weight = lambda r: lambda s, i, u: (u[r, s], 0, 0)
    specs = [pl.BlockSpec((1, bn, f, d_blk), rows),
             pl.BlockSpec((1, bn, f), row),
             pl.BlockSpec((1, bn, H), row)]
    specs += [pl.BlockSpec((1, bn, H), row)] * (int(with_g) + int(has_eb))
    specs.append(pl.BlockSpec((1, d_blk, H), weight(0)))
    if not shared_v:
        specs.append(pl.BlockSpec((1, d_blk, H), weight(1)))
    if has_post:  # the transforms are whole [H, H] blocks: no d_in chunk
        transform = (lambda s, i, c, u: (u[2, s], 0, 0)) if chunked else (
            lambda s, i, u: (u[2, s], 0, 0))
        specs += [pl.BlockSpec((1, H, H), transform)] * 2
    return specs


@functools.partial(
    jax.jit,
    static_argnames=("num_heads", "head_dim", "scale", "slope",
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
    in_specs = _attn_in_specs(bn, f, bc, H, has_eb, shared_v, has_post,
                              chunked=True)
    operands = [h, mask, qv] + [x for x in (eb, we, wv, pe, pv) if x is not None]
    scratch = [pltpu.VMEM((bn, f, H), jnp.float32)]
    if not shared_v:
        scratch.append(pltpu.VMEM((bn, f, H), jnp.float32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bn, H), lambda s, i, c, u: (s, i, 0)),
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        functools.partial(
            _attn_epilogue_kernel, n_chunks=grid[2], num_heads=nh, head_dim=dh,
            scale=scale, slope=slope, has_eb=has_eb, has_post=has_post,
            shared_v=shared_v,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rb, n, H), h.dtype),
        # the [bn, f, H] float32 accumulators (lane-padded to 128) overflow
        # the 16 MiB default scoped VMEM once HGT carries separate K and V
        # projections; v5e has 128 MiB per core
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_EPILOGUE_VMEM_LIMIT),
        name="stacked_attn_epilogue_pallas",
        interpret=interpret,
    )(us.astype(jnp.int32), *operands)


# --------------------------------------------------------------------------
# fused attention backward: recomputed per block, nothing saved per edge
# --------------------------------------------------------------------------


def _attn_bwd_kernel(u_ref, *refs, num_heads, head_dim, scale, slope, has_eb,
                     has_post, shared_v, h_grad):
    nh, dh = num_heads, head_dim
    it = iter(refs)
    h_ref, m_ref, qv_ref, g_ref = next(it), next(it), next(it), next(it)
    eb_ref = next(it) if has_eb else None
    we_ref = next(it)
    wv_ref = None if shared_v else next(it)
    pe_ref = next(it) if has_post else None
    pv_ref = next(it) if has_post else None
    dqv_ref = next(it)
    deb_ref = next(it) if has_eb else None
    dwe_ref = next(it)
    dwv_ref = None if shared_v else next(it)
    dpe_ref = next(it) if has_post else None
    dpv_ref = next(it) if has_post else None
    dh_ref = next(it) if h_grad else None

    # the slot's weight gradients accumulate in their output blocks, which
    # stay in VMEM while the node blocks of one slot go by
    @pl.when(pl.program_id(1) == 0)
    def _init():
        for r in (dwe_ref, dwv_ref, dpe_ref, dpv_ref):
            if r is not None:
                r[...] = jnp.zeros_like(r)

    h = h_ref[0]  # [bn, f, d_in]
    bn, f, d_in = h.shape
    H = nh * dh
    hf = h.reshape(bn * f, d_in)
    we = we_ref[0]
    # the forward's projections, recomputed in VMEM
    z0 = jax.lax.dot(hf.astype(we.dtype), we, preferred_element_type=jnp.float32)
    if shared_v:
        v0 = z0
    else:
        wv = wv_ref[0]
        v0 = jax.lax.dot(hf.astype(wv.dtype), wv,
                         preferred_element_type=jnp.float32)
    z3, v3 = z0.reshape(bn, f, H), v0.reshape(bn, f, H)
    qv = qv_ref[0].astype(jnp.float32)  # [bn, H]
    g = g_ref[0].astype(jnp.float32)  # [bn, H]
    qk = _rows_matmul(qv, pe_ref[0], transpose_w=True) if has_post else qv
    dov = _rows_matmul(g, pv_ref[0], transpose_w=True) if has_post else g
    eb = eb_ref[0].astype(jnp.float32) if has_eb else None
    e0, alpha = _attn_probs(z3, qk, m_ref[0], eb, nh=nh, dh=dh, scale=scale,
                            slope=slope)
    # closed-form softmax Jacobian (ops._sc_vjp_bwd's), head-expanded
    dalpha = _head_sum((v3 * dov[:, None, :]).reshape(bn * f, H), nh, dh)
    dalpha = dalpha.reshape(bn, f, H)
    de = alpha * (dalpha - jnp.sum(alpha * dalpha, axis=1, keepdims=True))
    if slope is not None:
        de = de * jnp.where(e0 >= 0, 1.0, slope).astype(de.dtype)
    if has_eb:
        deb_ref[0] = jnp.sum(de, axis=1).astype(deb_ref.dtype)
    des = de * scale
    dqk = jnp.sum(des * z3, axis=1)  # [bn, H]
    dz0 = (des * qk[:, None, :]).reshape(bn * f, H)
    dv0 = (alpha * dov[:, None, :]).reshape(bn * f, H)
    if has_post:
        dqv_ref[0] = _rows_matmul(dqk, pe_ref[0]).astype(dqv_ref.dtype)
        dpe_ref[0] += _rows_outer(dqk, qv)
        ov = jnp.sum(alpha * v3, axis=1)
        dpv_ref[0] += _rows_outer(ov, g)
    else:
        dqv_ref[0] = dqk.astype(dqv_ref.dtype)
    hx = hf.astype(jnp.float32)
    prec = jax.lax.Precision.DEFAULT
    if shared_v:
        dz0 = dz0 + dv0
        dwe_ref[0] += _rows_outer(hx, dz0, prec)
    else:
        dwe_ref[0] += _rows_outer(hx, dz0, prec)
        dwv_ref[0] += _rows_outer(hx, dv0, prec)
    if h_grad:
        dims = (((1,), (1,)), ((), ()))
        dh = jax.lax.dot_general(dz0.astype(we.dtype), we, dims,
                                 preferred_element_type=jnp.float32)
        if not shared_v:
            dh = dh + jax.lax.dot_general(dv0.astype(wv.dtype), wv, dims,
                                          preferred_element_type=jnp.float32)
        dh_ref[0] = dh.reshape(bn, f, d_in).astype(dh_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("num_heads", "head_dim", "scale", "slope", "h_grad",
                     "block_n", "interpret"),
)
def stacked_attn_bwd_pallas(
    h: jnp.ndarray,  # [rb, n, f, d_in]  (n pre-padded to block_n)
    mask: jnp.ndarray,  # [rb, n, f]
    qv: jnp.ndarray,  # [rb, n, H]
    g: jnp.ndarray,  # [rb, n, H] cotangent of the epilogue's output
    eb,  # [rb, n, H] head-expanded, or None
    we: jnp.ndarray,  # [Ue, d_in, H]
    wv,  # [Uv, d_in, H] or None (shares we)
    pe,  # [Ua, H, H] block-diagonal, or None
    pv,  # [Ua, H, H] block-diagonal, or None
    us: jnp.ndarray,  # [3, rb] int32 (scalar prefetch)
    num_heads: int,
    head_dim: int,
    scale: float = 1.0,
    slope=None,
    h_grad: bool = True,
    block_n: int = 128,
    interpret: bool = True,
):
    """The backward of :func:`stacked_attn_epilogue_pallas`, one grid step
    per (slot, node block), ``d_in`` whole.  Each step recomputes its
    block's projections, logits and softmax in VMEM from the forward's
    inputs and writes the gradients of the queries (``dqv``, ``[rb, n,
    H]``), of the additive logits (``deb``, head-expanded, where there are
    any) and, where ``h_grad``, of the neighbor rows (``dh``).  The weight
    gradients are per slot — ``dwe``/``dwv`` ``[rb, d_in, H]``, ``dpe``/
    ``dpv`` ``[rb, H, H]`` (only the diagonal blocks are meaningful) —
    summed over the slot's node blocks.  Returns a dict of those arrays."""
    rb, n, f, d_in = h.shape
    nh, dh = num_heads, head_dim
    H = nh * dh
    bn = block_n
    has_eb, has_post, shared_v = eb is not None, pe is not None, wv is None
    grid = (rb, pl.cdiv(n, bn))
    in_specs = _attn_in_specs(bn, f, d_in, H, has_eb, shared_v, has_post,
                              chunked=False, with_g=True)
    operands = [h, mask, qv, g] + [x for x in (eb, we, wv, pe, pv) if x is not None]

    row = pl.BlockSpec((1, bn, H), lambda s, i, u: (s, i, 0))
    outs = {"dqv": (row, (rb, n, H), h.dtype)}
    if has_eb:
        outs["deb"] = (row, (rb, n, H), h.dtype)
    slot_w = pl.BlockSpec((1, d_in, H), lambda s, i, u: (s, 0, 0))
    outs["dwe"] = (slot_w, (rb, d_in, H), jnp.float32)
    if not shared_v:
        outs["dwv"] = (slot_w, (rb, d_in, H), jnp.float32)
    if has_post:
        slot_p = pl.BlockSpec((1, H, H), lambda s, i, u: (s, 0, 0))
        outs["dpe"] = (slot_p, (rb, H, H), jnp.float32)
        outs["dpv"] = (slot_p, (rb, H, H), jnp.float32)
    if h_grad:
        outs["dh"] = (pl.BlockSpec((1, bn, f, d_in), lambda s, i, u: (s, i, 0, 0)),
                      (rb, n, f, d_in), h.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=[spec for spec, _, _ in outs.values()],
    )
    res = pl.pallas_call(
        functools.partial(
            _attn_bwd_kernel, num_heads=nh, head_dim=dh, scale=scale,
            slope=slope, has_eb=has_eb, has_post=has_post, shared_v=shared_v,
            h_grad=h_grad,
        ),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(shape, dt) for _, shape, dt in outs.values()],
        # the node-block axis revisits the slot's weight-gradient blocks
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_EPILOGUE_VMEM_LIMIT),
        name="stacked_attn_bwd_pallas",
        interpret=interpret,
    )(us.astype(jnp.int32), *operands)
    return dict(zip(outs, res))
