"""Operations and bytes of HGT's fused attention kernels, as they move them.

Counted as ``bench/flops.py`` says (the sampled fanout, each type's own
width, float32 values, one byte a mask entry, a multiply-add two
operations), per call of ``stacked_attn_epilogue_pallas`` (the forward) and
``stacked_attn_bwd_pallas`` (its backward), one call of each per level and
step.  For a relation at a level with ``n_prev`` parents, ``f`` sampled
neighbors each (``n = n_prev * f`` edges), input width ``di``, ``nh`` heads
of width ``dh`` (``H = nh * dh``):

forward
    K and V projections of every edge, ``2 * 2 n di H``; the query through
    W_ATT and the combined values through W_MSG, once per parent,
    ``2 * 2 n_prev H dh``; the logits and the weighted combine, ``2 * 2 n H``.
    It reads the edges' rows, the mask, the queries, the K, V, W_ATT and
    W_MSG weights, and writes one row per parent.
backward
    The forward's projections recomputed, ``2 * 2 n di H``, and its logits,
    ``2 n H``; per edge the probabilities' cotangent, the queries' cotangent
    summed over the fanout and the combined values, ``3 * 2 n H``, and the
    projections' cotangents, ``2 n H``; per parent the cotangent through
    W_MSG, the query through W_ATT and the gradients of the queries, W_ATT
    and W_MSG, ``5 * 2 n_prev H dh``; the K and V weight gradients,
    ``2 * 2 n di H``, and where the rows train their gradient, ``2 * 2 n di
    H``.  It reads what the forward reads and the output's cotangent, and
    writes the queries' gradient, each relation's weight gradients and,
    where the rows train, theirs.

The per-head transforms are counted at their own ``nh * dh * dh`` (the
kernels apply them as ``[H, H]`` block-diagonal matrices), and the fanout
unpadded (the kernels pad it to 8): both paddings are waste.  This replaces
``attn_epilogue_calls`` of ``bench/models/hgt.py``, which counts a forward
that writes its projections out and a separate input-gradient kernel.
"""

from __future__ import annotations

from bench.flops import _levels

FORWARD = "stacked_attn_epilogue_pallas"
BACKWARD = "stacked_attn_bwd_pallas"


def epilogue_calls(levels, H: int, nh: int):
    """One forward call a level: ``[{"flops", "bytes"}]``."""
    dh = H // nh
    calls = []
    for _, n_prev, f, rows in levels:
        n = n_prev * f
        fl = sum(4.0 * n * di * H + 4.0 * n_prev * H * dh + 4.0 * n * H
                 for _, di, _, _, _ in rows)
        by = sum(4 * (n * di + 2 * n_prev * H + 2 * di * H + 2 * nh * dh * dh) + n
                 for _, di, _, _, _ in rows)
        calls.append({"flops": fl, "bytes": by})
    return calls


def bwd_calls(levels, H: int, nh: int):
    """One backward call a level: ``[{"flops", "bytes"}]``."""
    dh = H // nh
    calls = []
    for _, n_prev, f, rows in levels:
        n = n_prev * f
        fl = by = 0.0
        for _, di, _, h_grad, _ in rows:
            fl += ((8.0 + 4.0 * h_grad) * n * di * H + 10.0 * n_prev * H * dh
                   + 10.0 * n * H)
            by += 4 * (n * di * (1 + h_grad) + 3 * n_prev * H + 4 * di * H
                       + 4 * nh * dh * dh) + n
        calls.append({"flops": fl, "bytes": by})
    return calls


def attn_calls(setup, batch: int):
    """Both kernels' calls in one training step, by kernel name, or None for
    a model other than hgt (whose epilogue these counts describe)."""
    if setup.model != "hgt":
        return None
    levels = list(_levels(setup, batch))
    return {FORWARD: epilogue_calls(levels, setup.hidden, setup.num_heads),
            BACKWARD: bwd_calls(levels, setup.hidden, setup.num_heads)}
