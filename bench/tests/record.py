"""Record one traced batch of a cell on the chip, for the tests.

    python bench/tests/record.py --workload <cell> --seed <n> \
        --out bench/tests/data/<name>.json.gz

Runs the cell once through ``bench/run.py``'s ``run`` with ``--trace 1``
(one traffic cycle), keeps the first traced batch of the profiler trace
(``Trace.window`` over its ``bench.batch`` span), cuts each operation's
HLO text to its name, opcode and custom-call target, and writes it as
gzipped JSON, with the op paths of the programs that ran in it as the
trace embeds them (``programs``: ``bench/hlo_scopes.py``).  The last line
of standard output is a JSON summary: the run's result line, the seconds
``trace_reduce.load`` and ``hlo_scopes.read`` took, whether the step
programs compiled again (``layer_time.step_hlo``) hold the op paths the
trace embeds (``step_hlo_is_traced``), each traced batch's duration, and
each step program's device seconds by layer scope
(``bench/layer_time.py``).
"""
from __future__ import annotations

import argparse
import dataclasses
import gzip
import json
import re
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import hlo_scopes, layer_time, run as run_mod  # noqa: E402
from bench import spec as spec_mod, trace_reduce  # noqa: E402
from bench import traffic as traffic_mod  # noqa: E402
from repro import configs  # noqa: E402

#: ``%name = <shape> opcode(``: the opcode is the first word before a "("
OPCODE = re.compile(r"\s([a-z][\w-]*)\(")
TARGET = re.compile(r'custom_call_target="[^"]*"')


def cut(hlo_text: str) -> str:
    """``%name = opcode(...)``, with the custom-call target where there is
    one; a text with no ``=`` is kept whole."""
    if " = " not in hlo_text:
        return hlo_text
    name, rest = hlo_text.split(" = ", 1)
    op = OPCODE.search(" " + rest)
    out = f"{name} = {op.group(1) if op else '?'}(...)"
    target = TARGET.search(rest)
    return f"{out}, {target.group(0)}" if target else out


def batch_trace(trace, programs, batch) -> dict:
    """The JSON of ``batch``'s part of ``trace``, with ``programs`` of the
    programs that ran in it."""
    t = trace.window(batch.start, batch.end)
    ran = {e.name for e in t.modules}
    out = trace_reduce.Trace(
        [dataclasses.replace(e, name=cut(e.name)) for e in t.ops], t.modules,
        t.host, t.n_devices).to_json()
    out["programs"] = {k: v for k, v in programs.items() if k in ran}
    return out


def traced_hlo(trace, programs, lo, hi) -> dict:
    """{"prefill": .., "decode": ..}: the op paths of the step programs
    that ran in [lo, hi), as the trace embeds them."""
    ran = {e.name for e in trace_reduce.executions(trace, lo, hi)}
    return {k: next((v for p, v in programs.items()
                     if p in ran and p.startswith(prefix)), None)
            for k, prefix in layer_time.PROGRAMS.items()}


def by_scope(trace, programs, lo, hi, gen) -> dict:
    hlo = traced_hlo(trace, programs, lo, hi)
    runs = layer_time.step_runs(trace, lo, hi, gen)
    if runs is None:
        return {}
    out = {}
    for program, rs in runs.items():
        s = layer_time.split(trace, rs, hlo[program] or {})
        out[program] = None if s is None else {
            "runs": s.runs, "seconds": s.total, "kernel_seconds": s.kernel,
            "unmatched_seconds": s.unmatched}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", type=Path, required=True)
    args = ap.parse_args(argv)
    cell = spec_mod.load(args.workload)
    gen = cell.traffic["gen"]
    with tempfile.TemporaryDirectory(prefix="bench-record-") as d:
        result = run_mod.run(cell, args.seed, 0.0, True, trace_dir=d)
        t0 = time.perf_counter()
        trace = trace_reduce.load(d)
        load_s = time.perf_counter() - t0
        path = sorted(Path(d).glob("**/*.xplane.pb"))[-1]
        t0 = time.perf_counter()
        programs = hlo_scopes.read(path.read_bytes())
        hlo_s = time.perf_counter() - t0
        xplane_bytes = path.stat().st_size
    batches = [e for e in trace.host if e.name == "bench.batch"]
    args.out.parent.mkdir(parents=True, exist_ok=True)
    with gzip.open(args.out, "wt") as f:
        json.dump(batch_trace(trace, programs, batches[0]), f)
    hlo = traced_hlo(trace, programs, batches[0].start, batches[0].end)
    B, S = traffic_mod.schedule(cell.traffic, args.seed, 1)[0]
    t0 = time.perf_counter()
    # past the cache of the run's own readers: what one reading costs
    compiled = layer_time.step_hlo.__wrapped__(
        configs.get(cell.config["program"]["arch"]), B, S, gen)
    step_hlo_s = time.perf_counter() - t0
    summary = {
        "result": result, "load_s": load_s, "hlo_read_s": hlo_s,
        "step_hlo_s": step_hlo_s, "step_hlo_is_traced": compiled == hlo,
        "xplane_bytes": xplane_bytes, "programs": len(programs),
        "batch_s": [b.dur for b in batches],
        "by_scope": [by_scope(trace, programs, b.start, b.end, gen)
                     for b in batches]}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
