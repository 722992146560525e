"""The part of ``attention_ms.decode`` outside Pallas kernels, in ms per
decode step: the projections, rope, the cache write, and the pads,
transposes, copies and slices around the decode attention kernel.  Read
through the trace's HLO (``bench/layer_time.py``)."""
from bench import layer_time


def read(ctx):
    return layer_time.ms_per_run(ctx, "decode", "attention",
                                 kernels=False)
