"""Device time of the attention layer (``attention`` scope: projections,
rope, cache write, the kernel wrapper's pads and transposes, the decode
attention kernel) per decode step of the traced batches, in ms.  Read
through the trace's HLO (``bench/layer_time.py``)."""
from bench import layer_time


def read(ctx):
    return layer_time.ms_per_run(ctx, "decode", "attention")
