"""Share of its roofline the backward of HGT's fused attention
(``stacked_attn_bwd_pallas``) reaches, in %: the least time the chip could
take for one step's calls (``bench/attn_calls.py``, the recomputed
projections counted as the kernel's work) over the kernel's device time per
traced step.  Nothing is read for another model, for a program without the
kernel, or when the trace holds another number of calls per step than the
step makes."""

from bench.attn_calls import BACKWARD, attn_calls
from bench.flops import roofline_share


def read(ctx):
    calls = attn_calls(ctx.setup, ctx.batch)
    return None if calls is None else roofline_share(ctx, {BACKWARD: calls[BACKWARD]})
