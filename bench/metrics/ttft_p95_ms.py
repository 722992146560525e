"""95th percentile (nearest rank) of time to first token over every
request of the window.  A request's TTFT is its batch's return time minus
its call time minus the batch's decode phase (``Generation.decode_s``):
building the cache, the per-call lower and compile, the prefill and the
first argmax.  The requests of one batch share the value."""
import math


def read(ctx):
    vals = sorted(ms for b in ctx.batches if not b.failed
                  for ms in [(b.t_ret - b.t_call - b.decode_s) * 1e3] * b.B)
    if not vals:
        return None
    return vals[math.ceil(0.95 * len(vals)) - 1]
