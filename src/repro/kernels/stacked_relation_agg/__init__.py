"""Stacked relation-aggregation kernel family (DESIGN.md §8).

One Pallas call per metatree level: grid over (branch slot, node block),
per-slot scope indices as scalar-prefetch operands so weight blocks are
read directly from the ``[U, ...]`` parameter stacks in HBM — no
materialized per-slot weight gather.  ``stacked_agg`` is the dispatch the
SPMD executor's ``_agg_level`` consumes; the gather-then-vmap oracle and
the grouped "stacked XLA" oracle live in ``ref``.
"""

from repro.kernels.stacked_relation_agg.ops import (  # noqa: F401
    fanout_tile,
    stacked_agg,
    stacked_agg_grouped,
    stacked_agg_ref,
    stacked_attn_bwd_block,
    stacked_attn_bwd_vmem_bytes,
    stacked_attn_epilogue,
    stacked_attn_epilogue_vmem_bytes,
    stacked_mean_linear,
    stacked_mean_linear_blocks,
    stacked_mean_linear_vmem_bytes,
    stacked_softmax_combine,
    stacked_softmax_combine_vmem_bytes,
)
