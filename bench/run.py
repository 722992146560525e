"""Run one benchmark cell on the chips of this machine and print one JSON
result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace 0|1

The cell is read by name (``bench/spec.py``): its configuration file, its
traffic file, its limits and its metrics.  The run

1. finds the chips (no TPU, or fewer chips than the cell asks for: exit
   code 2, no result line);
2. sets up: the program's config, the benchmark's own weights made on the
   chip from the seed in one jitted call, the prompt pool, one call of
   ``generate`` for each (B, S) of the traffic (``setup_s`` ends here);
3. measures: a closed loop of ``generate`` calls, one batch in flight,
   until ``--seconds`` have passed and the traffic's cycle in progress
   has ended (every batch counted in full, every seed the same mix).
   With ``--trace 1`` the profiler records the first ``trace_batches``
   batches of the window;
4. reads the memory peak, frees the program's state and checks the
   served tokens against the plain reference (``bench/check.py``);
5. prints the metrics: the cell's end-to-end metrics with ``--trace 0``,
   its per-layer metrics with ``--trace 1``; each is the value returned by
   ``bench/metrics/<name>.py``.

The numbers compared for ``correct`` are printed beside their limits, as
the last lines on standard error and under ``checks``, the result's last
key.  JAX's compilation cache is the program's (``repro.compile_cache``):
``.jax_cache/`` in the checkout unless ``JAX_COMPILATION_CACHE_DIR`` says
otherwise.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import functools  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bench import check, spec as spec_mod, trace_reduce  # noqa: E402
from bench import traffic as traffic_mod  # noqa: E402
from bench import program as program_mod  # noqa: E402

NO_CHIP = 2


@dataclasses.dataclass
class Batch:
    """One ``generate`` call of the window, timed on the host clock."""
    B: int
    S: int
    gen: int
    t_call: float
    t_ret: float
    compile_s: float = 0.0
    decode_s: float = 0.0
    failed: bool = False
    traced: bool = False
    prompts: object = None
    tokens: object = None
    last: object = None


@dataclasses.dataclass
class Context:
    """What a metric's reader may read (``bench/metrics/<name>.py``)."""
    cell: spec_mod.Cell
    spec: dict
    batches: list[Batch]
    setup_s: float
    #: host clock: the first call of the window, the last return
    window: tuple[float, float]
    device: dict
    peak: dict | None = None
    #: with --trace 1: the trace and the traced window on its clock
    trace: trace_reduce.Trace | None = None
    traced: tuple[float, float] | None = None
    #: the traced batches' spans on the trace's clock
    traced_spans: list[tuple[float, float]] = dataclasses.field(
        default_factory=list)


def device_info(chips: int) -> dict:
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu" or info["count"] < chips:
        print(f"no chips for this cell: it needs {chips} TPU, JAX found "
              f"{info}", file=sys.stderr)
        raise SystemExit(NO_CHIP)
    return info


def peak_of(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise SystemExit(f"device {kind!r} is not in bench/peaks.json")
    return table[kind]


def memory_peak() -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


def reader(name: str):
    """``read(ctx)`` of ``bench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@functools.partial(jax.jit, static_argnums=1)
def _pick(logits, vocab):
    return jnp.argmax(logits[:, :vocab], -1).astype(jnp.int32)


def serve_window(program, params, traffic, pool, seconds, trace_batches,
                 trace_dir):
    """The closed loop, in whole cycles of the traffic: it stops at the end
    of the cycle in which ``seconds`` ran out, so every seed sends the same
    mix.  Returns the batches and the window on the host clock; with
    ``trace_batches`` the first batches are traced into ``trace_dir``."""
    gen = traffic["gen"]
    cycle = traffic_mod.cycle_len(traffic)
    cfg = program.cfg
    vocab = cfg.vocab
    batches: list[Batch] = []
    tracing = trace_batches > 0
    span = None
    if tracing:
        # no Python tracer: it made a traced call about three times as long
        # on the host; host spans are the harness's and JAX's own
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        span = jax.profiler.TraceAnnotation("bench.traced")
        span.__enter__()
    t_first = time.perf_counter()
    deadline = t_first + seconds
    i = 0
    while True:
        prompts = pool[i % len(pool)]
        B, S = prompts.shape
        t_call = time.perf_counter()
        b = Batch(B, S, gen, t_call, t_call, traced=tracing, prompts=prompts)
        with jax.profiler.TraceAnnotation("bench.batch"):
            try:
                with jax.profiler.TraceAnnotation("bench.generate"):
                    out = program.generate(params, cfg, prompts, gen=gen)
                if out.tokens.shape != (B, gen) or len(out.logits) != gen + 1:
                    raise ValueError(f"served tokens {out.tokens.shape} and "
                                     f"{len(out.logits)} logits for "
                                     f"B={B}, gen={gen}")
                with jax.profiler.TraceAnnotation("bench.keep"):
                    b.tokens = out.tokens
                    b.last = _pick(out.logits[-1], vocab)
                    b.compile_s, b.decode_s = out.compile_s, out.decode_s
                    del out
            except Exception as e:          # noqa: BLE001 - counted, shown
                print(f"batch {i} (B={B}, S={S}) failed: {e!r}",
                      file=sys.stderr)
                b.failed = True
        b.t_ret = time.perf_counter()
        batches.append(b)
        i += 1
        if tracing and i == trace_batches:
            span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            tracing = False
        if b.t_ret >= deadline and i % cycle == 0:
            break
    if tracing:
        span.__exit__(None, None, None)
        jax.profiler.stop_trace()
    return batches, (t_first, batches[-1].t_ret)


def check_served(params, cell, batches, seed):
    """The numbers of ``check.compare`` for the served tokens of a seeded
    sample of the window's finished requests against the reference; None
    where no request finished."""
    reqs = []
    for b in batches:
        if b.failed:
            continue
        prompts = np.asarray(b.prompts)
        tokens = np.asarray(b.tokens)
        last = np.asarray(b.last)
        reqs.extend(check.Request(prompts[r], tokens[r], int(last[r]))
                    for r in range(b.B))
    if not reqs:
        return None
    n = cell.traffic["check_requests"]
    return check.compare(params, cell.config, check.sample(reqs, seed, n),
                         n)


def run(cell: spec_mod.Cell, seed: int, seconds: float, trace: bool, *,
        program=None, require_chip: bool = True, t0: float = _T0,
        trace_dir: str | None = None) -> dict:
    """One run of ``cell``; returns the result line as a dict.
    ``program`` replaces the program found from the configuration file
    (the tests' small sizes and planted faults); ``require_chip=False``
    skips the look for a chip (those tests, on the CPU)."""
    if require_chip:
        device = device_info(cell.chips)
        peak = peak_of(device["kind"])
    else:
        devs = jax.devices()
        device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs)}
        peak = None
    # every program of the run goes to the persistent cache, so only the
    # first run of a cell in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    spec = cell.config
    traffic = cell.traffic
    ref = check.reference(spec)
    if program is None:
        program = program_mod.load(spec)
    params = jax.jit(ref.make_params, static_argnums=0)(
        spec, traffic_mod.seed_key(seed, 0))
    jax.block_until_ready(params)
    program.check_layout(params)
    pool = traffic_mod.prompt_pool(traffic, seed, program.cfg.vocab)
    # warm up: one call for each shape of this traffic, and no other
    for shape in traffic_mod.shapes(traffic):
        first = next(p for p in pool if p.shape == shape)
        out = program.generate(params, program.cfg, first,
                               gen=traffic["gen"])
        jax.block_until_ready(_pick(out.logits[-1], program.cfg.vocab))
        del out
    setup_s = time.perf_counter() - t0

    own_dir = None
    if trace:
        trace_dir = trace_dir or (own_dir := tempfile.mkdtemp(
            prefix="bench-trace-"))
    batches, window = serve_window(
        program, params, traffic, pool, seconds,
        traffic["trace_batches"] if trace else 0, trace_dir)
    device["memory_peak_bytes"] = memory_peak()

    ctx = Context(cell=cell, spec=spec, batches=batches, setup_s=setup_s,
                  window=window, device=device, peak=peak)
    if trace:
        tr = trace_reduce.load(trace_dir)
        if own_dir:
            shutil.rmtree(own_dir, ignore_errors=True)
        spans = [(e.start, e.end) for e in tr.host if e.name == "bench.batch"]
        whole = next(e for e in tr.host if e.name == "bench.traced")
        ctx.trace, ctx.traced = tr, (whole.start, whole.end)
        ctx.traced_spans = spans
        device["busy_s"] = trace_reduce.busy(tr, *ctx.traced)
        device["window_s"] = whole.dur

    del pool
    read = check_served(params, cell, batches, seed) or {}
    failed = sum(b.B for b in batches if b.failed)
    checks = {name: {"value": read.get(name), "limit": limit}
              for name, limit in cell.limits.items()}
    checks["unservable"] = {"value": read.get("unservable"), "limit": 0}
    checks["failed"] = {"value": failed, "limit": 0}
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in checks.values())

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": correct,
              "attempted": sum(b.B for b in batches), "failed": failed,
              "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = trace_reduce.breakdown(ctx.trace, *ctx.traced)
    result["checks"] = checks
    print(f"checked {read.get('positions', 0)} served positions",
          file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec_mod.load(args.workload)
    result = run(cell, args.seed, args.seconds, bool(args.trace))
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
