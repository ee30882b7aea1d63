"""Public op: stacked relation aggregation — dispatch, padding, custom VJP.

:func:`stacked_agg` is the single entry point the SPMD executor's
``_agg_level`` calls per level (DESIGN.md §8).  Dispatch, driven by the
module's ``fused`` declaration and the resolved backend
(``repro.kernels.ops.kernel_choice``):

  * ``fused == "mean_linear"``     -> :func:`stacked_mean_linear` — the
    fully-fused Pallas kernel (scalar-prefetch slot→stack indirection).
  * ``fused == "softmax_combine"`` -> when the module declares an
    :meth:`~repro.core.relmod.RelationModule.attn_epilogue` (and
    ``fuse_epilogue`` is on), :func:`stacked_attn_epilogue` — the *fully
    fused* kernel whose per-slot logit/value projections stream from the
    ``[U, d_in, H]`` stacks via scalar prefetch (no materialized per-slot
    weight gather; custom VJP emits stack-form projection grads).
    Otherwise the oracle factoring: projections via the module's
    ``attn_parts`` (vmapped, XLA autodiff over gathered weights) + the
    Pallas masked softmax+combine epilogue.
  * ``fused is None`` (the module declares no kernel), or a non-TPU
    backend without forced interpret ->
    :func:`~repro.kernels.stacked_relation_agg.ref.stacked_agg_ref`, the
    gather-then-vmap oracle.  A module that declares a family but breaks
    its contract raises rather than falling back.

Both Pallas ops carry a ``jax.custom_vjp``:

  * ``stacked_mean_linear``'s backward produces the weight gradient
    **directly in stack form** ``[U, d_in, d_out]`` (per-slot contributions
    segment-summed over ``slot_u`` — autodiff of the gathered path would
    yield per-slot ``[rb, ...]`` grads scattered back afterwards), and the
    neighbor-activation gradient through the scalar-prefetch ``dh`` kernel,
    so the backward reads weights from the stack exactly like the forward.
    Cross-*shard* sharing stays ``sync_stack_grads``' job: this op sums
    within a shard's slots, the executor's existing sync sums across
    shards' stack rows — composition, no overlap.
  * ``stacked_softmax_combine``'s backward is the closed-form softmax
    Jacobian (recomputed probabilities, no saved alpha), matching autodiff
    of ``relmod.masked_softmax`` including the all-masked-row case.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.custom_derivatives import SymbolicZero

from repro.kernels.ops import (
    agg_blocks,
    agg_vmem_bytes,
    clamp_block,
    kernel_choice,
    pad_axes,
    pad_to,
    resolve_blocks,
    zero_cotangent,
)
from repro.kernels.stacked_relation_agg.kernel import (
    _EPILOGUE_VMEM_LIMIT,
    stacked_attn_bwd_pallas,
    stacked_attn_epilogue_pallas,
    stacked_mean_linear_dh_pallas,
    stacked_mean_linear_pallas,
    stacked_softmax_combine_pallas,
)
from repro.kernels.stacked_relation_agg.ref import stacked_agg_grouped, stacked_agg_ref

__all__ = [
    "stacked_agg",
    "stacked_mean_linear",
    "stacked_softmax_combine",
    "stacked_attn_epilogue",
    "stacked_agg_ref",
    "stacked_agg_grouped",
    "stacked_mean_linear_blocks",
    "stacked_mean_linear_vmem_bytes",
    "stacked_softmax_combine_vmem_bytes",
    "stacked_attn_epilogue_vmem_bytes",
    "stacked_attn_bwd_vmem_bytes",
    "stacked_attn_bwd_block",
    "fanout_tile",
]


# --------------------------------------------------------------------------
# block derivation + VMEM accounting (single source for op and benchmarks)
# --------------------------------------------------------------------------


# the stacked forward's per-step working set matches the unstacked kernel's
# (the slot axis contributes a block edge of 1) — one shared formula in the
# ops layer, so BENCH figures can never drift from the dispatch
stacked_mean_linear_blocks = agg_blocks
stacked_mean_linear_vmem_bytes = agg_vmem_bytes


def stacked_softmax_combine_vmem_bytes(
    n: int, f: int, num_heads: int, head_dim: int,
    block_n: int = 128, bytes_per_elem: int = 4,
) -> int:
    bn = clamp_block(block_n, n)
    H = num_heads * head_dim
    elems = bn * f * num_heads + bn * f + bn * f * H + bn * H
    return elems * bytes_per_elem


# the attention kernels fold [bn, f, .] blocks into [bn*f, .] matmul rows;
# with the fanout padded to whole sublane tiles that fold is free for Mosaic
# (an unaligned one compiles to relayouts, slowly and into more VMEM).
# Padded slots are masked out, so they change nothing.
_F_TILE = 8


def fanout_tile(module, opts=None) -> int:
    """The tile :func:`stacked_agg` pads a level's fanout to: ``_F_TILE``
    where the module's attention runs the fused epilogue kernels, 1 (no
    padding) elsewhere.  A producer that lays the neighbor rows out at the
    padded fanout (``repro.core.raf_spmd.gather_leaf``) spares the op its
    padded copy."""
    use, _ = kernel_choice(opts, "stacked_agg")
    fused = (use and module.fused == "softmax_combine"
             and getattr(opts, "fuse_epilogue", True))
    return _F_TILE if fused else 1


def _lanes(x: int) -> int:
    """A minor dim as VMEM lays it out: whole 128-lane tiles."""
    return -(-x // 128) * 128


def stacked_attn_epilogue_vmem_bytes(
    n: int, f: int, d_in: int, num_heads: int, head_dim: int,
    block_n: int = 128, block_in: int = 512,
    shared_v: bool = True, bytes_per_elem: int = 4,
) -> int:
    """Per-grid-step working set of the fused attention AGG_r: h block +
    mask + qv + streamed weight tile(s) + out tile (input dtype) plus the
    float32 projection accumulator(s)."""
    bn = clamp_block(block_n, n)
    bc = clamp_block(block_in, d_in)
    H = num_heads * head_dim
    n_acc = 1 if shared_v else 2
    elems = bn * f * bc + bn * f + bn * H + n_acc * bc * H + bn * H
    return elems * bytes_per_elem + n_acc * bn * f * H * 4


# float32 [bn*f, H] temporaries the attention backward holds at once: the
# projections z0 and v0, the logits, the probabilities, their cotangent,
# the logits' cotangent and the projections' cotangents dz0 and dv0
_BWD_LIVE = 8


def stacked_attn_bwd_vmem_bytes(
    n: int, f: int, d_in: int, num_heads: int, head_dim: int,
    block_n: int = 128, shared_v: bool = False, has_post: bool = True,
    has_eb: bool = False, h_grad: bool = True, bytes_per_elem: int = 4,
) -> int:
    """Per-grid-step working set of :func:`stacked_attn_bwd_pallas`, minor
    dims lane-padded: its input and output blocks, each double-buffered
    (the neighbor rows and their gradient, mask, queries, cotangent,
    projection and transform blocks, the slot's weight-gradient blocks),
    plus the float32 per-edge temporaries of one node block.  ``f`` is the
    fanout before its padding to whole sublane tiles."""
    bn = clamp_block(block_n, n)
    f8 = -(-f // _F_TILE) * _F_TILE
    H, D = _lanes(num_heads * head_dim), _lanes(d_in)
    rows = bn * f8
    n_w = 1 if shared_v else 2
    blocks = (rows * D * (2 if h_grad else 1) + bn * _lanes(f8)
              + bn * H * (3 + 2 * has_eb)  # qv, g, dqv (+ eb, deb)
              + 2 * n_w * D * H + 4 * has_post * H * H)
    return 2 * blocks * bytes_per_elem + _BWD_LIVE * rows * H * 4


# the backward's working-set budget: half the scoped VMEM both attention
# kernels ask for, the rest left to the compiler's own temporaries
_BWD_VMEM_BUDGET = _EPILOGUE_VMEM_LIMIT // 2


def stacked_attn_bwd_block(
    n: int, f: int, d_in: int, num_heads: int, head_dim: int,
    block_n: int = 128, **kw,
) -> int:
    """The backward's node block: the forward's (``clamp_block(block_n,
    n)``), halved while its working set (:func:`stacked_attn_bwd_vmem_bytes`,
    with ``kw``) is over ``_BWD_VMEM_BUDGET`` and it stays a multiple of 8
    that divides the forward's block."""
    bb = clamp_block(block_n, n)
    while (bb % 16 == 0 and stacked_attn_bwd_vmem_bytes(
            n, f, d_in, num_heads, head_dim, block_n=bb, **kw) > _BWD_VMEM_BUDGET):
        bb //= 2
    return bb


# --------------------------------------------------------------------------
# mean_linear: fused Pallas forward + stack-form custom VJP
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _MLCfg:
    bn: int
    bo: int
    bc: int
    interpret: bool


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _stacked_ml(cfg: _MLCfg, h, mask, w, b, slot_u):
    return _ml_fwd_impl(cfg, h, mask, w, b, slot_u)


def _ml_fwd_impl(cfg, h, mask, w, b, slot_u):
    rb, n, f, d_in = h.shape
    d_out = w.shape[2]
    hp = pad_axes(h, {1: cfg.bn, 3: cfg.bc})
    mp = pad_to(mask, 1, cfg.bn)
    wp = pad_axes(w, {1: cfg.bc, 2: cfg.bo})
    bp = pad_to(b, 1, cfg.bo)[:, None, :]
    out = stacked_mean_linear_pallas(
        hp, mp, wp, bp, slot_u,
        block_n=cfg.bn, block_out=cfg.bo, block_in=cfg.bc, interpret=cfg.interpret,
    )
    return out[:, :n, :d_out]


def _ml_vjp_fwd(cfg, h, mask, w, b, slot_u):
    return _ml_fwd_impl(cfg, h, mask, w, b, slot_u), (h, mask, w, slot_u)


def _ml_vjp_bwd(cfg, res, g):
    h, mask, w, slot_u = res
    rb, n, f, d_in = h.shape
    U, _, d_out = w.shape
    # dh through the scalar-prefetch kernel — weight blocks read from the
    # stack, same indirection as the forward
    gp = pad_axes(g, {1: cfg.bn, 2: cfg.bo})
    mp = pad_to(mask, 1, cfg.bn)
    wp = pad_axes(w, {1: cfg.bc, 2: cfg.bo})
    dh = stacked_mean_linear_dh_pallas(
        gp, mp, wp, slot_u,
        block_n=cfg.bn, block_out=cfg.bo, block_in=cfg.bc, interpret=cfg.interpret,
    )[:, :n, :, :d_in]
    # dw/db accumulate straight into the [U, ...] stack: per-slot outer
    # products segment-summed over slot_u (slots sharing a stack row sum,
    # exactly like autodiff of the dict-form forward sums occurrences)
    mw = mask.astype(h.dtype)
    cnt = jnp.maximum(mw.sum(-1, keepdims=True), 1.0)
    mean = jnp.sum(h * mw[..., None], axis=2) / cnt
    pw = jnp.einsum("rnd,rno->rdo", mean, g)
    dw = jax.ops.segment_sum(pw, slot_u, num_segments=U)
    db = jax.ops.segment_sum(jnp.sum(g, axis=1), slot_u, num_segments=U)
    return dh, zero_cotangent(mask), dw, db, zero_cotangent(slot_u)


_stacked_ml.defvjp(_ml_vjp_fwd, _ml_vjp_bwd)


def stacked_mean_linear(
    h: jnp.ndarray,  # [rb, n, f, d_in]
    mask: jnp.ndarray,  # [rb, n, f]
    w: jnp.ndarray,  # [U, d_in, d_out]
    b: jnp.ndarray,  # [U, d_out]
    slot_u: jnp.ndarray,  # [rb] int
    block_n: int = 128,
    block_out: int = 128,
    block_in: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    rb, n, f, d_in = h.shape
    bn, bo, bc = stacked_mean_linear_blocks(
        n, f, d_in, w.shape[2], block_n, block_out, block_in
    )
    cfg = _MLCfg(bn, bo, bc, bool(interpret))
    return _stacked_ml(cfg, h, mask, w, b, slot_u.astype(jnp.int32))


# --------------------------------------------------------------------------
# softmax_combine: Pallas epilogue + closed-form custom VJP
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _SCCfg:
    bn: int
    num_heads: int
    head_dim: int
    interpret: bool


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _stacked_sc(cfg: _SCCfg, e, mask, v):
    return _sc_fwd_impl(cfg, e, mask, v)


def _sc_fwd_impl(cfg, e, mask, v):
    rb, n, f, nh = e.shape
    vf = v.reshape(rb, n, f, nh * cfg.head_dim)
    ep = pad_to(_head_expand(e, cfg.head_dim), 1, cfg.bn)
    mp = pad_to(mask, 1, cfg.bn)
    vp = pad_to(vf, 1, cfg.bn)
    out = stacked_softmax_combine_pallas(
        ep, mp, vp, block_n=cfg.bn, interpret=cfg.interpret,
    )
    return out[:, :n]


def _head_expand(x, dh: int):
    """``[..., nh] -> [..., nh*dh]``: each head's value on all its lanes."""
    return jnp.repeat(x, dh, axis=-1)


def _block_diag(p):
    """Per-head transforms ``[U, nh, dh, dh]`` -> block-diagonal
    ``[U, nh*dh, nh*dh]`` (``x @ bd`` applies head ``h``'s matrix to lanes
    ``h*dh:(h+1)*dh``)."""
    U, nh, dh, _ = p.shape
    eye = jnp.eye(nh, dtype=p.dtype)
    return jnp.einsum("uhde,hk->uhdke", p, eye).reshape(U, nh * dh, nh * dh)


def _sc_alpha(e, mask):
    neg = jnp.asarray(jnp.finfo(e.dtype).min, e.dtype)
    em = jnp.where(mask[:, :, :, None], e, neg)
    em = em - jnp.max(em, axis=2, keepdims=True)
    z = jnp.exp(em) * mask[:, :, :, None].astype(e.dtype)
    return z / jnp.maximum(jnp.sum(z, axis=2, keepdims=True), 1e-9)


def _sc_vjp_fwd(cfg, e, mask, v):
    return _sc_fwd_impl(cfg, e, mask, v), (e, mask, v)


def _sc_vjp_bwd(cfg, res, g):
    e, mask, v = res
    rb, n, f, nh = e.shape
    alpha = _sc_alpha(e, mask)  # [rb, n, f, nh]
    gh = g.reshape(rb, n, nh, cfg.head_dim)
    dalpha = jnp.einsum("rnfhd,rnhd->rnfh", v, gh)
    tot = jnp.sum(alpha * dalpha, axis=2, keepdims=True)
    de = alpha * (dalpha - tot)
    dv = jnp.einsum("rnfh,rnhd->rnfhd", alpha, gh)
    return de, zero_cotangent(mask), dv


_stacked_sc.defvjp(_sc_vjp_fwd, _sc_vjp_bwd)


def stacked_softmax_combine(
    e: jnp.ndarray,  # [rb, n, f, nh]
    mask: jnp.ndarray,  # [rb, n, f]
    v: jnp.ndarray,  # [rb, n, f, nh, dh]
    block_n: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    rb, n, f, nh = e.shape
    dh = v.shape[-1]
    cfg = _SCCfg(clamp_block(block_n, n), nh, dh, bool(interpret))
    return _stacked_sc(cfg, e, mask, v)


# --------------------------------------------------------------------------
# fully fused attention epilogue: stack-streamed projections, custom VJP
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _AECfg:
    bn: int
    bc: int
    bb: int  # node block of the backward
    nh: int
    dh: int
    scale: float
    slope: object  # Optional[float]
    has_eb: bool
    has_post: bool
    shared_v: bool
    interpret: bool


def _ae_operands(cfg, h, mask, qv, eb, we, wv, pe, pv):
    """The kernels' operands: padded to their blocks, the additive logits
    head-expanded, the per-head transforms block-diagonal; None where the
    module has no such operand."""
    return (
        pad_axes(h, {1: cfg.bn, 2: _F_TILE, 3: cfg.bc}),
        pad_axes(mask, {1: cfg.bn, 2: _F_TILE}),
        pad_to(qv, 1, cfg.bn),
        pad_to(_head_expand(eb, cfg.dh), 1, cfg.bn) if cfg.has_eb else None,
        pad_to(we, 1, cfg.bc),
        None if cfg.shared_v else pad_to(wv, 1, cfg.bc),
        _block_diag(pe) if cfg.has_post else None,
        _block_diag(pv) if cfg.has_post else None,
    )


def _ae_forward(cfg, ops, us):
    return stacked_attn_epilogue_pallas(
        *ops, us, num_heads=cfg.nh, head_dim=cfg.dh, scale=cfg.scale,
        slope=cfg.slope, block_n=cfg.bn, block_in=cfg.bc,
        interpret=cfg.interpret,
    )


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _stacked_ae(cfg: _AECfg, h, mask, qv, eb, we, wv, pe, pv, us):
    n = h.shape[1]
    return _ae_forward(cfg, _ae_operands(cfg, h, mask, qv, eb, we, wv, pe, pv),
                       us)[:, :n]


def _ae_vjp_fwd(cfg, h, mask, qv, eb, we, wv, pe, pv, us):
    # the residuals are the kernel's own (padded) inputs: the backward
    # recomputes the projections block by block, so nothing with a row per
    # edge slot is kept.  The neighbor rows' gradient is written only where
    # they are differentiated (``perturbed``; not the fixed rows of a leaf
    # level)
    h_grad = h.perturbed
    h, mask, qv, eb, we, wv, pe, pv, us = (
        x.value for x in (h, mask, qv, eb, we, wv, pe, pv, us))
    ops = _ae_operands(cfg, h, mask, qv, eb, we, wv, pe, pv)
    return (_ae_forward(cfg, ops, us)[:, :h.shape[1]],
            (ops, us, h.shape, h_grad))


def _diag_blocks(x, nh: int, dh: int):
    """``[r, H, H]`` -> its ``nh`` diagonal ``[dh, dh]`` blocks ``[r, nh, dh, dh]``."""
    r = x.shape[0]
    return jnp.moveaxis(
        jnp.diagonal(x.reshape(r, nh, dh, nh, dh), axis1=1, axis2=3), -1, 1)


def _ae_vjp_bwd(cfg, res, g):
    ops, us, (rb, n, f, d_in), h_grad = res
    hp, mp, qp, _, we, wv, pe, pv = ops
    if isinstance(g, SymbolicZero):
        g = jnp.zeros(g.shape, g.dtype)
    grads = stacked_attn_bwd_pallas(
        hp, mp, qp, pad_to(g, 1, cfg.bn), *ops[3:], us,
        num_heads=cfg.nh, head_dim=cfg.dh, scale=cfg.scale, slope=cfg.slope,
        h_grad=h_grad, block_n=cfg.bb, interpret=cfg.interpret,
    )
    # per-slot weight gradients straight into stack form (segment-summed
    # over slot rows; cross-shard sharing stays sync_stack_grads' job)
    seg = lambda x, u, like: jax.ops.segment_sum(
        x, u, num_segments=like.shape[0]).astype(like.dtype)
    dwe = seg(grads["dwe"][:, :d_in], us[0], we)
    dwv = None if cfg.shared_v else seg(grads["dwv"][:, :d_in], us[1], wv)
    dpe = dpv = None
    if cfg.has_post:
        dpe = seg(_diag_blocks(grads["dpe"], cfg.nh, cfg.dh), us[2], pe)
        dpv = seg(_diag_blocks(grads["dpv"], cfg.nh, cfg.dh), us[2], pv)
    deb = grads["deb"][:, :n, ::cfg.dh] if cfg.has_eb else None
    dh_ = grads["dh"][:, :n, :f, :d_in] if h_grad else None
    return (dh_, None, grads["dqv"][:, :n], deb, dwe, dwv, dpe, dpv, None)


_stacked_ae.defvjp(_ae_vjp_fwd, _ae_vjp_bwd, symbolic_zeros=True)
# called through jit: shard_map's eager path does not take a custom_vjp with
# symbolic zeros, a staged call does
_stacked_ae_call = jax.jit(_stacked_ae, static_argnums=0)


def stacked_attn_epilogue(
    epi,  # relmod.AttnEpilogue
    h: jnp.ndarray,  # [rb, n, f, d_in]
    mask: jnp.ndarray,  # [rb, n, f]
    block_n: int = 128,
    block_in: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fully fused attention AGG_r from canonical epilogue operands."""
    rb, n, f, d_in = h.shape
    nh, dh = epi.num_heads, epi.head_dim
    shared_v = epi.wv is None
    has_post = epi.pe is not None
    ue = epi.ue.astype(jnp.int32)
    uv = ue if epi.uv is None else epi.uv.astype(jnp.int32)
    ua = jnp.zeros_like(ue) if epi.ua is None else epi.ua.astype(jnp.int32)
    us = jnp.stack([ue, uv, ua])
    dummy = jnp.zeros((1, 1, 1), h.dtype)
    bn = clamp_block(block_n, n)
    cfg = _AECfg(
        bn=bn, bc=clamp_block(block_in, d_in),
        # sized for the larger working set, the rows' gradient included
        bb=stacked_attn_bwd_block(
            n, f, d_in, nh, dh, block_n=bn, shared_v=shared_v,
            has_post=has_post, has_eb=epi.eb is not None),
        nh=nh, dh=dh, scale=float(epi.scale),
        slope=None if epi.slope is None else float(epi.slope),
        has_eb=epi.eb is not None, has_post=has_post, shared_v=shared_v,
        interpret=bool(interpret),
    )
    out = _stacked_ae_call(
        cfg, h, mask, epi.qv,
        dummy if epi.eb is None else epi.eb,
        epi.we,
        dummy if shared_v else epi.wv,
        dummy if not has_post else epi.pe,
        dummy if not has_post else epi.pv,
        us,
    )
    return out if epi.bias is None else out + epi.bias[:, None, :]


# --------------------------------------------------------------------------
# the executor entry point
# --------------------------------------------------------------------------


def stacked_agg(
    module,
    stacks: Dict[str, jnp.ndarray],  # {leaf: [U_scope, ...]} one shard's slabs
    slot_u: Dict[str, jnp.ndarray],  # {scope: [rb] int} per-slot stack rows
    h: jnp.ndarray,  # [rb, n, f, d_in]
    q: jnp.ndarray,  # [rb, n, d_dst]
    mask: jnp.ndarray,  # [rb, n, f]
    opts=None,
    block_n: Optional[int] = None,
    block_out: Optional[int] = None,
    block_in: Optional[int] = None,
) -> jnp.ndarray:
    """One level's AGG_r for every branch slot (see module docstring).

    Block sizes resolve per (op, shape-class): explicit kwargs beat the
    ``opts`` overrides beat the committed tuning table (``opts.autotune``)
    beat the defaults — see ``repro.kernels.ops.resolve_blocks``."""
    use, interp = kernel_choice(opts, "stacked_agg")
    rb, n, f, d_in = h.shape

    def _blocks(op: str, d_out: int):
        bn, bo, bc = resolve_blocks(opts, op, n, f, d_in, d_out)
        return (block_n or bn, block_out or bo, block_in or bc)

    scope_of = {s.name: s.scope for s in module.specs}
    if use and module.fused == "mean_linear":
        # the family contract is leaves named w/b sharing one scope; a module
        # that declares the family without it is a bug, never a quiet detour
        # through the oracle
        if scope_of.get("w") is None or scope_of.get("w") != scope_of.get("b"):
            raise ValueError(
                f"relation module {module.name!r} declares fused='mean_linear' "
                f"but not its contract (leaves 'w' and 'b' in one scope); "
                f"leaves: {scope_of}")
        bn, bo, bc = _blocks("stacked_mean_linear", stacks["w"].shape[2])
        return stacked_mean_linear(
            h, mask, stacks["w"], stacks["b"], slot_u[scope_of["w"]],
            block_n=bn, block_out=bo, block_in=bc, interpret=interp,
        )
    if use and module.fused == "softmax_combine":
        if getattr(opts, "fuse_epilogue", True):
            bn, bo, bc = _blocks("stacked_attn_epilogue",
                                 _epilogue_width(module, stacks))
            epi = module.attn_epilogue(
                stacks, slot_u, q,
                linear=partial(_epilogue_linear, block_n=bn, block_out=bo,
                               block_in=bc, interpret=interp),
            )
            if epi is not None:
                return stacked_attn_epilogue(
                    epi, h, mask, block_n=bn, block_in=bc, interpret=interp,
                )
        # attn_parts oracle path (fuse_epilogue off, or no epilogue decl):
        # projections vmapped under XLA autodiff over gathered weights
        p_slots = {name: stacks[name][slot_u[scope_of[name]]] for name in stacks}
        e, v = jax.vmap(module.attn_parts)(p_slots, h, q)
        nh_, dh_ = v.shape[3], v.shape[4]
        bn, _, _ = _blocks("stacked_softmax_combine", nh_ * dh_)
        out = stacked_softmax_combine(
            e, mask, v, block_n=bn, interpret=interp
        )
        bias = module.attn_bias(p_slots)  # [rb, hidden] or None
        return out if bias is None else out + bias[:, None, :]
    if use and module.fused is not None:
        raise ValueError(
            f"relation module {module.name!r} declares an unknown fused "
            f"kernel family {module.fused!r}")
    # no kernel chosen, or the module declares none (fused=None): its own
    # aggregate, vmapped over the slots, is the computation
    return stacked_agg_ref(module, stacks, slot_u, h, q, mask)


def _epilogue_width(module, stacks) -> int:
    """The attention hidden width nh*dh — the widest last dim among the
    module's ``[U, d, hidden]`` projection stacks."""
    return max(s.shape[-1] for s in stacks.values() if s.ndim == 3)


def _epilogue_linear(w_stack, u, x, *, block_n, block_out, block_in, interpret):
    """Per-slot projection ``x @ w_stack[u]`` for the q-side of an
    attention epilogue — routed through :func:`stacked_mean_linear` with a
    singleton fanout (masked mean over one slot is the identity), so the
    weight blocks stream from the stack and the VJP lands in stack form."""
    rb, n, d = x.shape
    zb = jnp.zeros((w_stack.shape[0], w_stack.shape[2]), w_stack.dtype)
    ones = jnp.ones((rb, n, 1), bool)
    return stacked_mean_linear(
        x[:, :, None, :], ones, w_stack, zb, u,
        block_n=block_n, block_out=block_out, block_in=block_in,
        interpret=interpret,
    )
