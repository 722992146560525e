"""Device time of the expert layer (``experts`` scope: router, dispatch,
expert matmuls, combine) per decode step of the traced batches, in ms.
Read through the trace's HLO (``bench/layer_time.py``)."""
from bench import layer_time


def read(ctx):
    return layer_time.ms_per_run(ctx, "decode", "experts")
