"""Readings that set a cell's limits: the program's gaps and the
control's, seed after seed, in one process (set-up compiles once).

    python bench/calibrate.py --workload <name> --seconds <s> --seeds 1 2 ...

For each seed: the benchmark's weights and prompts from that seed, a
window of ``--seconds`` at the cell's own load (``bench/run.py``'s closed
loop), then on the same seeded sample of finished requests

* ``program``: the numbers of ``check.compare`` (widest and mean gap,
  positions, unservable) for the program's served tokens;
* ``control``: the same for the tokens that the reference in fp8 puts
  first (``bench/check.py``);

one JSON line per seed.  The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench import check, program as program_mod, run  # noqa: E402
from bench import spec as spec_mod, traffic as traffic_mod  # noqa: E402


def readings(cell, prog, seed: int, seconds: float) -> dict:
    spec, traffic = cell.config, cell.traffic
    ref = check.reference(spec)
    params = jax.jit(ref.make_params, static_argnums=0)(
        spec, traffic_mod.seed_key(seed, 0))
    prog.check_layout(params)
    pool = traffic_mod.prompt_pool(traffic, seed, prog.cfg.vocab)
    batches, window = run.serve_window(prog, params, traffic, pool, seconds,
                                       0, None)
    reqs = []
    for b in batches:
        prompts, tokens, last = (np.asarray(x) for x in
                                 (b.prompts, b.tokens, b.last))
        reqs.extend(check.Request(prompts[r], tokens[r], int(last[r]))
                    for r in range(b.B))
    n = traffic["check_requests"]
    picked = check.sample(reqs, seed, n)
    out = {"seed": seed, "batches": len(batches),
           "window_s": window[1] - window[0],
           "longest": max(len(r.prompt) for r in picked)}
    t = time.perf_counter()
    out["program"] = check.compare(params, spec, picked, n)
    out["reference_s"] = time.perf_counter() - t
    out["control"] = check.compare(params, spec, picked, n, quant=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = spec_mod.load(args.workload)
    device = run.device_info(cell.chips)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    prog = program_mod.load(cell.config)
    for seed in args.seeds:
        r = readings(cell, prog, seed, args.seconds)
        print(json.dumps(dict(r, workload=cell.name, device=device)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
