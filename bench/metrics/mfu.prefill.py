"""Model FLOP utilisation of the prefill programs: the operations the
prefill calls of the traced batches need (``bench/flops.py``: routed
experts only, causal attention at head_dim 64, the head at the last
position) over their device time (the prefill program's executions in
the trace) times the chip's bf16 peak, in percent."""
from bench import flops, serve_trace


def read(ctx):
    if ctx.trace is None or ctx.peak is None:
        return None
    ops = secs = 0.0
    for b, (lo, hi) in serve_trace.traced_batches(ctx):
        runs = serve_trace.step_runs(ctx.trace, lo, hi, b.gen)
        if runs is None:
            return None
        ops += flops.prefill_flops(ctx.spec, b.B, b.S)
        secs += runs[0].dur
    return 100.0 * ops / (secs * ctx.peak["bf16_flops"]) if secs else None
