"""Share of its roofline the stacked mean-linear kernel family reaches, in %:
the least time the chip could take for one step's calls (``mean_linear_calls``
of ``bench/models/<model>.py``: per call the larger of operations over peak
and bytes over bandwidth, ``bench/flops.py``) over the kernels' device time
per traced step.  Nothing is read when the trace holds another number of
calls per step than the step makes."""

from bench.flops import kernel_calls
from bench.flops import roofline_share


def read(ctx):
    return roofline_share(ctx, kernel_calls(ctx.setup, ctx.batch, "mean_linear"))
