"""Share of its roofline that the decode attention kernel reaches: the
least time for the traced decode steps' attention (K and V read once per
KV head up to the keys that exist, ``bench/flops.py``) over the kernel's
summed device time in the decode executions, in percent."""
from bench import flops, serve_trace


def read(ctx):
    if ctx.trace is None or ctx.peak is None:
        return None
    least = secs = 0.0
    layers = ctx.spec["num_hidden_layers"]
    for b, (lo, hi) in serve_trace.traced_batches(ctx):
        runs = serve_trace.step_runs(ctx.trace, lo, hi, b.gen)
        if runs is None:
            return None
        for i in range(b.gen):
            t, _ = flops.least_time(*flops.decode_attention_cost(
                ctx.spec, b.B, b.S + i + 1), ctx.peak)
            least += layers * t
        secs += serve_trace.kernel_seconds(ctx.trace, runs[1:])
    return 100.0 * least / secs if secs else None
