"""Share of the traced window in which no operation ran on the chip:
1 - (union of the device's operation intervals / window), in percent,
averaged over the chips.  The window is the traced batches' span."""
from bench import trace_reduce


def read(ctx):
    if ctx.trace is None or not ctx.trace.ops:
        return None
    lo, hi = ctx.traced
    return 100.0 * (1.0 - trace_reduce.busy(ctx.trace, lo, hi) / (hi - lo))
