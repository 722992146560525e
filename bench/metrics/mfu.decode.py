"""Model FLOP utilisation of the decode programs: the operations of the
traced batches' decode steps (step i of a batch of S-token prompts
attends to S + i + 1 keys) over the decode executions' device time
times the chip's bf16 peak, in percent."""
from bench import flops, serve_trace


def read(ctx):
    if ctx.trace is None or ctx.peak is None:
        return None
    ops = secs = 0.0
    for b, (lo, hi) in serve_trace.traced_batches(ctx):
        runs = serve_trace.step_runs(ctx.trace, lo, hi, b.gen)
        if runs is None:
            return None
        ops += sum(flops.decode_flops(ctx.spec, b.B, b.S + i + 1)
                   for i in range(b.gen))
        secs += sum(r.dur for r in runs[1:])
    return 100.0 * ops / (secs * ctx.peak["bf16_flops"]) if secs else None
