"""Paper §7.3: the 43-design frequency study (headline table).

For every design: baseline = packed placement, no pipelining (the default
tool flow); TAPA = the §6.3 joint design-space search over the max-util
sweep (all knob points evaluated, Pareto-pruned, best frontier candidate
kept), replacing the old first-feasible retry loop.  Frequencies come from
the calibrated physical-design surrogate; throughput (cycle) preservation
is checked by dataflow simulation on *every* run.

Cross-design batching: the search phase defers simulation
(``prepare_design_space``), and then ONE ``simulate_batch`` call scores
every design's baseline + all candidates for the whole suite — the padded
ragged-batch backend vectorizes across the heterogeneous topologies, so
the suite's simulation phase is a single array-sweep instead of one
Python-level engine run per design.  The JSON summary records the engine
invocation counters, backends used and simulation wall-time so CI can
verify the fast subset never degrades to per-job event simulation.

Paper targets: baseline avg 147 MHz (failures counted as 0), optimized avg
297 MHz; 16/43 baseline failures, all recovered (avg 274 MHz).

Converged mode (``--converge``): every design instead runs
``search_until_converged`` over a *continuous* util range anchored on the
discrete UTIL_SWEEP grid — refine rounds re-anchor on the incumbent Pareto
frontier, all rounds share one ``FloorplanCache`` and the round-1 baseline
simulation, and the JSON ``sim`` block records ``floorplan`` solve/cache-hit
counters plus ``points_evaluated`` so the CI gate can *prove* the
memoization fired (cache hits > 0, solves < points).  Because the anchors
are exactly the default path's sweep, a converged run's frontier can never
score below the non-converged baseline JSON it is gated against.

``--jobs N`` fans each round's cold ILP solves over the
``repro.search.pool`` worker pool: results are bit-identical to ``--jobs
1`` (the CI gate compares a ``--jobs 2`` run's rows against the fresh
sequential converged JSON and requires exact frontier identity), the
search wall time drops with cores, and the ``sim.pool`` block records the
worker dispatch/merge counters plus the parent-side merged floorplan
counts.  ``--proposer surrogate`` switches the round proposals to the
response-surface model (``repro.search.surrogate``).

``--backend`` pins the ``simulate_batch`` backend for the suite's
simulation phase (default ``auto``: the jax-jitted sweep when jax is
importable, the NumPy sweep otherwise).  A ``--backend jax`` run records
the jitted sweep's compile-cache counters (``sim.jit_cache``) and a
*measured* NumPy-vs-jax ``sim.speedup`` block — the CI jax leg gates that
run row-exact against a fresh NumPy JSON (``check_jax_backend``).

Crash-safety (``docs/robustness-guide.md``): ``--store DIR`` keeps the
converged run's floorplan solves in a content-addressed
``DiskFloorplanStore`` shared across designs and *runs* (the JSON
``sim.store`` block records writes/hits/quarantined-entry counts), and
``--checkpoint DIR`` journals each design's search per round so a killed
suite resumes from the last completed round with bit-identical rows —
the chaos CI job (``benchmarks/chaos_suite.py``) SIGKILLs a run mid-suite
under seeded fault injection and gates the resumed rows against a clean
run.  The ``sim.faults`` block records injected-vs-observed fault counts
(all zero on a clean run).

CLI:
    python benchmarks/fmax_suite.py [--subset fast|full] [--json PATH]
                                    [--firings N] [--no-sim] [--converge]
                                    [--jobs N] [--proposer uniform|surrogate]
                                    [--backend auto|numpy|jax|event]
                                    [--store DIR] [--checkpoint DIR]
"""
from __future__ import annotations

import argparse
import json
import os
import time

from repro import compile_cache
from repro.analysis import analysis_counts, reset_analysis_counts
from repro.core import (FloorplanCache, InfeasibleError, Interval,
                        SearchPoint, SearchSpace, analyze_timing,
                        engine_counts, floorplan_counts, packed_placement,
                        prepare_design_space, reset_engine_counts,
                        reset_floorplan_counts, search_until_converged,
                        timed_pool_simulations)
from repro.fpga import benchmarks as B, grid_for
from repro.obs import bench_obs_block, trace as obs_trace
from repro.search import (DiskFloorplanStore, FaultPlan, fault_counts,
                          install_faults, pool_counts, reset_fault_counts,
                          reset_pool_counts, reset_store_counts,
                          store_counts)
from repro.search.faults import active_plan
from repro.search.pool import pool_task_stats
from repro.search.store import store_lookup_stats

UTIL_SWEEP = (0.70, 0.75, 0.80, 0.85, 0.90, 0.95, 1.0)

#: small, quick designs exercised by the CI bench-regression gate; the full
#: 43-design table runs nightly.
FAST_SUBSET = ("stencil_x2", "stencil_x4", "cnn_13x2", "gaussian_12",
               "bucket_sort", "page_rank")

#: throughput-preservation firings used by the default path (satisfies the
#: ROADMAP item: cycles are checked on every run, not a spot-check subset)
DEFAULT_FIRINGS = 200

#: converged-mode budget: refine rounds per design and configurations per
#: round (round 1 = the UTIL_SWEEP anchors + random draws from the
#: continuous range; later rounds = frontier anchors + refined draws)
CONVERGE_ROUNDS = 3
CONVERGE_POINTS = 12


def prepare(name: str, board: str, graph) -> dict:
    """Baseline timing + deferred candidate search for one design (no
    simulation yet — that happens once for the whole suite)."""
    grid = grid_for(board)
    base_pl = packed_placement(graph, grid)
    base = analyze_timing(graph, grid, base_pl)
    t0 = time.monotonic()
    prep = prepare_design_space(graph, grid,
                                space=SearchSpace(seeds=(0,),
                                                  utils=UTIL_SWEEP))
    wall = time.monotonic() - t0
    return {"name": name, "board": board, "graph": graph, "grid": grid,
            "base_pl": base_pl, "base": base, "prep": prep, "wall_s": wall}


def score_all(entries: list[dict], sim_firings: int | None,
              backend: str = "auto") -> dict | None:
    """The suite's entire simulation phase: one ``simulate_batch`` call
    over every design's baseline + feasible candidates (mixed topologies
    vectorize through the padded backend).  Returns the recorded metadata
    (engine counters, backends, wall time; plus the jit compile-cache and
    the measured NumPy-vs-jax speedup for ``backend="jax"`` runs) or None
    when sim is disabled."""
    if not sim_firings:
        return None
    _, meta = timed_pool_simulations([e["prep"] for e in entries],
                                     firings=sim_firings, backend=backend,
                                     measure_speedup=(backend == "jax"))
    return meta


def assemble_row(name: str, board: str, graph, grid, base_pl, base, res, *,
                 wall: float, sim_firings: int | None) -> dict:
    """Best-candidate resolution (with the unroutable fallback) plus the
    row schema shared by the default and converged paths.  One definition
    on purpose: ``check_regression`` compares both paths' rows against the
    same committed baseline, so the schemas must never drift.

    ``res`` is anything with ``.best`` (raising ``InfeasibleError`` when no
    candidate routes) and ``.frontier`` — a ``SearchResult`` or a
    ``ConvergedSearch``."""
    cand = None
    try:
        cand = res.best
        util, opt = cand.point.max_util, cand.report
        overhead = cand.plan.area_overhead
    except InfeasibleError as e:
        util, overhead = None, 0.0
        opt = analyze_timing(graph, grid, base_pl)
        opt.routed, opt.fmax_mhz, opt.fail_reason = False, 0.0, str(e)
    row = {
        "name": name, "board": board,
        "tasks": graph.num_tasks, "streams": graph.num_streams,
        "base_mhz": base.fmax_mhz if base.routed else 0.0,
        "base_fail": None if base.routed else base.fail_reason,
        "opt_mhz": opt.fmax_mhz if opt.routed else 0.0,
        "opt_fail": None if opt.routed else opt.fail_reason,
        "util": util, "wall_s": wall,
        "buffer_overhead_bits": overhead,
        "frontier": len(res.frontier),
    }
    if sim_firings and cand is not None and cand.sim is not None:
        # throughput preservation by dataflow simulation (paper Tables 4-7):
        # scored for every candidate inside the batched call(s).
        row["cycles_base"] = cand.base_sim.cycles
        row["cycles_opt"] = cand.sim.cycles
        row["cycles_delta"] = cand.sim.cycles - cand.base_sim.cycles
        row["sim_deadlock"] = cand.sim.deadlocked
        row["throughput_preserved"] = cand.throughput_preserved
        row["backend_used"] = cand.sim.engine
    return row


def finish(entry: dict, sim_firings: int | None) -> dict:
    """Frontier + row assembly for one prepared (and batch-scored) design."""
    res = entry["prep"].finish(sim_calls=1 if sim_firings else 0)
    return assemble_row(entry["name"], entry["board"], entry["graph"],
                        entry["grid"], entry["base_pl"], entry["base"], res,
                        wall=entry["wall_s"], sim_firings=sim_firings)


def run_converged(name: str, board: str, graph, *, sim_firings: int | None,
                  cache: FloorplanCache, jobs: int = 1,
                  proposer: str = "uniform",
                  backend: str = "auto",
                  checkpoint: str | None = None) -> dict:
    """One design through ``search_until_converged``: continuous util range
    anchored on the discrete UTIL_SWEEP grid, shared floorplan cache.
    ``jobs`` fans the cold ILP solves over the worker pool (bit-identical
    rows, less wall time); ``proposer`` selects the round-proposal model;
    ``checkpoint`` journals the search per round for kill-resume."""
    grid = grid_for(board)
    base_pl = packed_placement(graph, grid)
    base = analyze_timing(graph, grid, base_pl)
    anchors = [SearchPoint(seed=0, max_util=u) for u in UTIL_SWEEP]
    t0 = time.monotonic()
    res = search_until_converged(
        graph, grid,
        space=SearchSpace(utils=Interval(UTIL_SWEEP[0], UTIL_SWEEP[-1])),
        rounds=CONVERGE_ROUNDS, points_per_round=CONVERGE_POINTS,
        sim_firings=sim_firings, initial_points=anchors, cache=cache,
        jobs=jobs, proposer=proposer, sim_backend=backend,
        checkpoint=checkpoint)
    row = assemble_row(name, board, graph, grid, base_pl, base, res,
                       wall=time.monotonic() - t0, sim_firings=sim_firings)
    row.update({
        "rounds_run": res.rounds_run,
        "converged": res.converged,
        "points_evaluated": res.points_evaluated,
        "hypervolume": res.hypervolumes[-1] if res.hypervolumes else 0.0,
        "proposer": res.proposer,
        "resumed_rounds": res.resumed_rounds,
    })
    return row


def summarize(rows: list[dict]) -> dict:
    n = len(rows)
    fails = [r for r in rows if r["base_fail"]]
    recovered = [r for r in fails if not r["opt_fail"]]
    routable = [r for r in rows if not r["base_fail"]]
    return {
        "designs": n,
        "base_avg_mhz": sum(r["base_mhz"] for r in rows) / n,
        "opt_avg_mhz": sum(r["opt_mhz"] for r in rows) / n,
        "baseline_fails": len(fails),
        "recovered": len(recovered),
        "recovered_avg_mhz": (sum(r["opt_mhz"] for r in recovered)
                              / len(recovered) if recovered else 0.0),
        "routable_base_avg_mhz": (sum(r["base_mhz"] for r in routable)
                                  / max(len(routable), 1)),
        "sim_deadlocks": sum(1 for r in rows if r.get("sim_deadlock")),
        "throughput_violations": sum(
            1 for r in rows if r.get("throughput_preserved") is False),
        "cycles_delta_total": sum(r.get("cycles_delta", 0) for r in rows),
    }


def main(verbose: bool = True, sim_firings: int | None = DEFAULT_FIRINGS,
         subset: tuple[str, ...] | None = None,
         json_path: str | None = None,
         backend: str = "auto",
         trace_path: str | None = None) -> list[dict]:
    reset_analysis_counts()
    obs_trace.enable(clear=True)
    t0 = time.monotonic()
    with obs_trace.span("bench.suite", suite="fmax"):
        with obs_trace.span("bench.prepare"):
            entries = [prepare(name, board, graph)
                       for name, board, graph in B.autobridge_suite()
                       if subset is None or name in subset]
        sim_meta = score_all(entries, sim_firings, backend)
        with obs_trace.span("bench.finish"):
            rows = [finish(entry, sim_firings) for entry in entries]
    if verbose:
        for r in rows:
            base = f"{r['base_mhz']:.0f}" if not r["base_fail"] else "FAIL"
            opt = f"{r['opt_mhz']:.0f}" if not r["opt_fail"] else "FAIL"
            cyc = (f" cycles_delta={r['cycles_delta']}"
                   if "cycles_delta" in r else "")
            print(f"fmax_suite,{r['name']}@{r['board']},{r['wall_s']*1e6:.0f},"
                  f"base={base}MHz opt={opt}MHz util={r['util']}{cyc}")
    obs_block = bench_obs_block(time.monotonic() - t0, trace_path)
    if sim_meta is not None:
        sim_meta["obs"] = obs_block
    print(f"fmax_suite,OBS,0,spans={obs_block['spans']} "
          f"coverage={obs_block['stage_coverage']:.2f}"
          + (f" trace={obs_block['trace_file']}" if trace_path else ""))
    s = summarize(rows)
    print(f"fmax_suite,SUMMARY,0,designs={s['designs']} "
          f"base_avg={s['base_avg_mhz']:.0f}MHz (paper 147) "
          f"opt_avg={s['opt_avg_mhz']:.0f}MHz (paper 297) "
          f"baseline_fails={s['baseline_fails']} (paper 16) "
          f"recovered={s['recovered']} "
          f"recovered_avg={s['recovered_avg_mhz']:.0f}MHz (paper 274) "
          f"routable_base_avg={s['routable_base_avg_mhz']:.0f}MHz (paper 234) "
          f"deadlocks={s['sim_deadlocks']}")
    if sim_meta:
        print(f"fmax_suite,SIM,0,jobs={sim_meta['jobs']} "
              f"invocations={sim_meta['invocations']} "
              f"backends={'+'.join(sim_meta['backends'])} "
              f"wall={sim_meta['wall_s']:.3f}s")
        if sim_meta.get("speedup"):
            sp = sim_meta["speedup"]
            print(f"fmax_suite,SPEEDUP,0,numpy={sp['numpy_wall_s']:.3f}s "
                  f"jax={sp['jax_wall_s']:.3f}s x{sp['speedup']:.1f}")
    if json_path:
        with open(json_path, "w") as f:
            json.dump({"suite": "fmax_suite", "sim_firings": sim_firings,
                       "subset": sorted(subset) if subset else None,
                       "backend": backend,
                       "rows": rows, "summary": s, "sim": sim_meta},
                      f, indent=2)
        print(f"fmax_suite,JSON,0,wrote {json_path}")
    return rows


def main_converged(verbose: bool = True,
                   sim_firings: int | None = DEFAULT_FIRINGS,
                   subset: tuple[str, ...] | None = None,
                   json_path: str | None = None,
                   jobs: int = 1,
                   proposer: str = "uniform",
                   backend: str = "auto",
                   store: str | None = None,
                   checkpoint: str | None = None,
                   trace_path: str | None = None) -> list[dict]:
    """The ``--converge`` path: per-design ``search_until_converged`` with a
    suite-wide ``FloorplanCache``; the JSON ``sim`` block carries the
    floorplan solve/cache-hit counters the CI gate checks, plus the
    ``pool`` worker dispatch/merge counters when ``jobs > 1`` (the
    parallel-run gate requires them and exact row identity vs the
    sequential run).  ``store`` swaps the suite cache for a
    ``DiskFloorplanStore`` (adds the ``sim.store`` block); ``checkpoint``
    journals each design's search under ``DIR/<name>@<board>`` so a killed
    suite run resumes — completed designs replay from their final
    checkpoint, the interrupted one continues from its last round."""
    reset_engine_counts()
    reset_floorplan_counts()
    reset_pool_counts()
    reset_analysis_counts()
    reset_store_counts()
    reset_fault_counts()
    obs_trace.enable(clear=True)
    cache = DiskFloorplanStore(store) if store else FloorplanCache()
    t0 = time.monotonic()
    rows = []
    with obs_trace.span("bench.suite", suite="fmax", mode="converged"):
        for name, board, graph in B.autobridge_suite():
            if subset is not None and name not in subset:
                continue
            ckpt = (os.path.join(checkpoint, f"{name}@{board}")
                    if checkpoint else None)
            with obs_trace.span("bench.design", design=f"{name}@{board}"):
                r = run_converged(name, board, graph,
                                  sim_firings=sim_firings,
                                  cache=cache, jobs=jobs, proposer=proposer,
                                  backend=backend, checkpoint=ckpt)
            rows.append(r)
            if verbose:
                base = (f"{r['base_mhz']:.0f}" if not r["base_fail"]
                        else "FAIL")
                opt = f"{r['opt_mhz']:.0f}" if not r["opt_fail"] else "FAIL"
                print(f"fmax_suite,{r['name']}@{r['board']},"
                      f"{r['wall_s']*1e6:.0f},"
                      f"base={base}MHz opt={opt}MHz util={r['util']} "
                      f"rounds={r['rounds_run']} converged={r['converged']} "
                      f"points={r['points_evaluated']}")
    obs_block = bench_obs_block(time.monotonic() - t0, trace_path)
    fp = floorplan_counts()
    pool = {"jobs": jobs, **pool_counts(), "task_s": pool_task_stats()}
    ana = analysis_counts()
    plan = active_plan()
    # always emitted — zeroed (enabled=False) when no --store was given —
    # so the store gate can never pass by silently not running
    store_block = dict(store_counts())
    store_block["enabled"] = isinstance(cache, DiskFloorplanStore)
    store_block["entries"] = (cache.disk_entries()
                              if store_block["enabled"] else 0)
    store_block["lookup_s"] = store_lookup_stats()
    faults_block = {
        "plan": plan.as_dict() if plan is not None else None,
        "injected": fault_counts(),
        "observed": {k: pool[k] for k in ("retried", "timed_out",
                                          "quarantined", "pool_rebuilds")}
        | {"store_quarantined": store_counts()["quarantined"],
           "merge_conflicts": fp["merge_conflicts"]},
    }
    from repro.kernels.sim_sweep import sweep_cache_stats
    sim_meta = {"firings": sim_firings, "mode": "converged",
                "counts": engine_counts(), "floorplan": fp,
                "cache": cache.stats(), "pool": pool,
                "analysis": ana, "jit_cache": sweep_cache_stats(),
                "store": store_block, "faults": faults_block,
                "proposer": proposer, "backend": backend,
                "points_evaluated": sum(r["points_evaluated"] for r in rows),
                "wall_s": time.monotonic() - t0,
                "obs": obs_block}
    s = summarize(rows)
    print(f"fmax_suite,SUMMARY,0,designs={s['designs']} "
          f"opt_avg={s['opt_avg_mhz']:.0f}MHz (converged) "
          f"deadlocks={s['sim_deadlocks']}")
    print(f"fmax_suite,FLOORPLAN,0,solved={fp['solved']} "
          f"cache_hits={fp['cache_hits']} "
          f"ilp_bipartitions={fp['ilp_bipartitions']} "
          f"points={sim_meta['points_evaluated']}")
    print(f"fmax_suite,POOL,0,jobs={jobs} "
          f"dispatched={pool['dispatched']} merged={pool['merged']} "
          f"worker_solves={pool['worker_solves']} "
          f"search_wall={sim_meta['wall_s']:.2f}s")
    print(f"fmax_suite,ANALYSIS,0,analyzed={ana['analyzed']} "
          f"doomed={ana['doomed']} skipped={ana['skipped']} "
          f"infeasible={ana['infeasible']}")
    print(f"fmax_suite,OBS,0,spans={obs_block['spans']} "
          f"coverage={obs_block['stage_coverage']:.2f}"
          + (f" trace={obs_block['trace_file']}" if trace_path else ""))
    if store_block["enabled"]:
        print(f"fmax_suite,STORE,0,entries={store_block['entries']} "
              f"writes={store_block['writes']} "
              f"disk_hits={store_block['disk_hits']} "
              f"quarantined={store_block['quarantined']}")
    if plan is not None:
        obs = faults_block["observed"]
        print(f"fmax_suite,FAULTS,0,injected={faults_block['injected']} "
              f"retried={obs['retried']} timed_out={obs['timed_out']} "
              f"quarantined={obs['quarantined']} "
              f"pool_rebuilds={obs['pool_rebuilds']}")
    if json_path:
        with open(json_path, "w") as f:
            json.dump({"suite": "fmax_suite", "converge": True,
                       "sim_firings": sim_firings,
                       "subset": sorted(subset) if subset else None,
                       "backend": backend,
                       "rows": rows, "summary": s, "sim": sim_meta},
                      f, indent=2)
        print(f"fmax_suite,JSON,0,wrote {json_path}")
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--subset", choices=("fast", "full"), default="full",
                    help="fast = CI bench-regression subset; full = all 43")
    ap.add_argument("--json", dest="json_path", default=None,
                    help="write rows + summary as JSON (BENCH_fmax.json)")
    ap.add_argument("--firings", type=int, default=DEFAULT_FIRINGS,
                    help="throughput-sim firings per task (0 disables)")
    ap.add_argument("--no-sim", action="store_true",
                    help="skip throughput simulation entirely")
    ap.add_argument("--converge", action="store_true",
                    help="run search_until_converged per design (continuous "
                         "util range, memoized floorplans, cache stats in "
                         "the JSON sim block)")
    ap.add_argument("--jobs", type=int, default=1,
                    help="worker processes for the converged search's cold "
                         "ILP floorplan solves (1 = sequential; results "
                         "are bit-identical either way)")
    ap.add_argument("--proposer", choices=("uniform", "surrogate"),
                    default="uniform",
                    help="converged-search round-proposal strategy")
    ap.add_argument("--backend", choices=("auto", "numpy", "jax", "event"),
                    default="auto",
                    help="simulate_batch backend for the simulation phase "
                         "(jax additionally records sim.jit_cache and a "
                         "measured sim.speedup block)")
    ap.add_argument("--store", default=None, metavar="DIR",
                    help="converged mode: persist floorplan solves to a "
                         "content-addressed DiskFloorplanStore at DIR "
                         "(shared across designs and runs; sim.store block)")
    ap.add_argument("--checkpoint", default=None, metavar="DIR",
                    help="converged mode: journal each design's search per "
                         "round under DIR so a killed run resumes with "
                         "bit-identical rows")
    ap.add_argument("--trace", dest="trace_path", default=None,
                    metavar="PATH",
                    help="write the run's span trace as Chrome/Perfetto "
                         "trace_event JSON at PATH (open in ui.perfetto.dev"
                         "; summarize with python -m repro.obs)")
    ap.add_argument("--faults", default=None, metavar="JSON",
                    help="converged mode: run under this seeded fault plan "
                         "(a repro.search.FaultPlan as JSON; the chaos "
                         "drill's)")
    args = ap.parse_args()
    compile_cache.configure()
    sim = None if args.no_sim else (args.firings or None)
    subset = FAST_SUBSET if args.subset == "fast" else None
    if args.converge:
        plan = (FaultPlan.from_dict(json.loads(args.faults)) if args.faults
                else None)
        with install_faults(plan):
            main_converged(sim_firings=sim, subset=subset,
                           json_path=args.json_path, jobs=args.jobs,
                           proposer=args.proposer, backend=args.backend,
                           store=args.store, checkpoint=args.checkpoint,
                           trace_path=args.trace_path)
    else:
        main(sim_firings=sim, subset=subset, json_path=args.json_path,
             backend=args.backend, trace_path=args.trace_path)
