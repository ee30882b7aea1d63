"""Share of its roofline HGT's fused attention forward
(``stacked_attn_epilogue_pallas``) reaches, in %: the least time the chip
could take for one step's calls (``bench/attn_calls.py``: per call the
larger of operations over peak and bytes over bandwidth) over the kernel's
device time per traced step.  Nothing is read for another model, or when
the trace holds another number of calls per step than the step makes."""

from bench.attn_calls import FORWARD, attn_calls
from bench.flops import roofline_share


def read(ctx):
    calls = attn_calls(ctx.setup, ctx.batch)
    return None if calls is None else roofline_share(ctx, {FORWARD: calls[FORWARD]})
