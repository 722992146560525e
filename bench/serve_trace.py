"""What the serving metrics read from a trace.

In each traced batch (a ``bench.batch`` span), the model step runs once
as the prefill and then once per decode step.  On the TPU an operation's
name is its HLO text, and the program gives its Pallas kernels no names
of their own yet, so a kernel is found by its custom-call target.  The
kernels inside the layer loop (within the interval of the run's ``while``
operation) are the model's attention kernels: in a prefill run
``flash_attention``, in a decode run ``decode_attention``; the one outside
it is the embedding gather."""
from __future__ import annotations

import re

from bench import trace_reduce

#: what marks a Pallas kernel's operation in the trace
KERNEL = 'custom_call_target="tpu_custom_call"'
#: a while loop's operation, by its HLO text (``%while.4 = (...) while(...)``)
LOOP_OP = re.compile(r"\swhile\(")


def step_runs(trace, lo: float, hi: float, gen: int):
    """[prefill, decode 0, ..., decode gen-1] executions of the step
    program on chip 0 in [lo, hi), or None where they cannot be told.

    The prefill and decode programs may carry one name (gen + 1 runs) or
    two (one run, then gen runs); of the candidates, the one with the
    most device time is taken (the argmax after each step also runs
    gen + 1 times, briefly)."""
    by: dict[str, list] = {}
    for e in trace_reduce.executions(trace, lo, hi):
        by.setdefault(e.name, []).append(e)
    candidates = [runs for runs in by.values() if len(runs) == gen + 1]
    for decode in (runs for runs in by.values() if len(runs) == gen):
        before = [r[0] for r in by.values()
                  if len(r) == 1 and r[0].end <= decode[0].start]
        if before:
            candidates.append([max(before, key=lambda e: e.dur)] + decode)
    if not candidates:
        return None
    return max(candidates, key=lambda runs: sum(e.dur for e in runs))


def layer_kernels(trace, run) -> list:
    """The Pallas kernels of one run that lie in its layer loop."""
    ops = trace_reduce.ops_within(trace, run)
    loops = [(o.start, o.end) for o in ops if LOOP_OP.search(o.name)]
    return [op for op in ops if KERNEL in op.name and any(
        lo <= op.start and op.end <= hi + 1e-9 for lo, hi in loops)]


def kernel_seconds(trace, runs) -> float:
    """Device seconds of the layer-loop kernels in ``runs``."""
    return sum(op.dur for run in runs for op in layer_kernels(trace, run))


def traced_batches(ctx):
    """(batch, (lo, hi)) for each traced batch, on the trace's clock."""
    traced = [b for b in ctx.batches if b.traced]
    return list(zip(traced, ctx.traced_spans))
