"""Multi-device sweeps, adaptive refinement, the FIFO-sizing area credit,
and the cross-design benchmark batching acceptance.

Covers the new one-call sweep path: ``prepare_design_space`` defers
simulation, ``sweep_backends`` scores several device grids' candidates in
one batched call, ``SearchSpace.refine`` zooms sampling into the frontier
neighborhood, ``analyze_timing(buffer_bits=...)`` charges buffering into
slot utilization (so profile-driven FIFO sizing credits reclaimed bits
back as fmax), and the fmax suite's simulation phase is a single padded
array-sweep across heterogeneous designs.
"""
import importlib.util
import os

import pytest

from repro.core import (PhysicalModel, SearchPoint, SearchSpace,
                        TaskGraphBuilder, analyze_timing,
                        explore_design_space, sweep_backends)
from repro.core import explorer as explorer_mod
from repro.fpga import grid_for, tpu_pod_grid, u250_grid, u280_grid

def _vecadd(pe=4):
    b = TaskGraphBuilder("VecAdd")
    a = b.streams("str_a", n=pe, width=512)
    bb = b.streams("str_b", n=pe, width=512)
    c = b.streams("str_c", n=pe, width=512)
    b.invoke("LoadA", area={"LUT": 12e3, "BRAM": 30, "hbm_channels": 1},
             outs=a, count=pe)
    b.invoke("LoadB", area={"LUT": 12e3, "BRAM": 30, "hbm_channels": 1},
             outs=bb, count=pe)
    b.invoke("Add", area={"LUT": 60e3, "DSP": 256}, ins=a + bb, outs=c,
             count=pe)
    b.invoke("Store", area={"LUT": 12e3, "hbm_channels": 1}, ins=c, count=pe)
    return b.build()


# ---------------------------------------------------------------------------
# sweep_backends: one batched call across device grids
# ---------------------------------------------------------------------------


def test_sweep_backends_single_batched_call(monkeypatch):
    """U250 + U280 + a TPU-pod grid: every grid's baseline + candidates
    scored by exactly one ``simulate_batch`` call; per-grid results match
    a standalone ``explore_design_space`` run."""
    graph = _vecadd()
    space = SearchSpace(utils=(0.7, 0.8))
    calls = []
    real_batch = explorer_mod.simulate_batch

    def counting_batch(jobs, **kw):
        jobs = list(jobs)
        calls.append(len(jobs))
        return real_batch(jobs, **kw)

    monkeypatch.setattr(explorer_mod, "simulate_batch", counting_batch)
    grids = {"u250": u250_grid(), "u280": u280_grid(),
             "tpu": tpu_pod_grid(2, 2)}
    sweep = sweep_backends(graph, grids, space=space, sim_firings=80)
    assert len(calls) == 1 and sweep.sim_calls == 1
    assert set(sweep.results) == set(grids)
    for name, res in sweep.results.items():
        assert res.space_size == space.size
        for c in res.candidates:
            if c.plan is not None:
                assert c.sim is not None and c.base_sim is not None
        # matches a standalone per-grid search (same knobs, own batch call)
        solo = explore_design_space(graph, grids[name], space=space,
                                    sim_firings=80)
        assert [c.fmax for c in res.candidates] == \
            [c.fmax for c in solo.candidates]
        assert [(c.sim.cycles, c.sim.deadlocked)
                for c in res.candidates if c.sim] == \
            [(c.sim.cycles, c.sim.deadlocked)
             for c in solo.candidates if c.sim]
    name, best = sweep.best
    assert name in grids and best.report.routed
    rows = sweep.table()
    assert {r["grid"] for r in rows} == set(grids)
    assert all(r["fmax_mhz"] > 0 for r in rows if r["routable"])


def test_sweep_backends_accepts_grid_sequences():
    graph = _vecadd()
    sweep = sweep_backends(graph, [u280_grid(), u280_grid()],
                           space=SearchSpace(utils=(0.8,)), sim_firings=40)
    assert set(sweep.results) == {"U280", "U280#2"}
    with pytest.raises(ValueError):
        sweep_backends(graph, [], sim_firings=40)


def test_device_grid_registry():
    assert grid_for("u250").name == "U250"
    assert grid_for("tpu_pod_4x2").rows == 4
    with pytest.raises(KeyError):
        grid_for("nonesuch")


# ---------------------------------------------------------------------------
# SearchSpace.refine
# ---------------------------------------------------------------------------


def test_refine_zooms_into_frontier_neighborhood():
    space = SearchSpace(seeds=(0, 1, 2), utils=(0.6, 0.7, 0.8),
                        depth_scales=(1.0, 2.0, 4.0))
    frontier = [SearchPoint(seed=1, max_util=0.7, depth_scale=2.0)]
    pts = space.refine(frontier, 50, seed=9)
    assert pts and len(pts) == len(set(pts))
    # seeds restricted to the frontier's; numeric axes stay within one
    # original-grid step of the frontier values (midpoint halving)
    for p in pts:
        assert p.seed == 1
        assert 0.6 <= p.max_util <= 0.8
        assert 1.0 <= p.depth_scale <= 4.0
    # midpoints toward the adjacent original values are present
    utils = {p.max_util for p in pts}
    for want in (0.65, 0.7, 0.75):
        assert any(abs(u - want) < 1e-9 for u in utils), (want, utils)
    # deterministic and capped by the refined-space size
    assert pts == space.refine(frontier, 50, seed=9)
    # n smaller than the neighborhood samples without replacement
    assert len(space.refine(frontier, 3, seed=0)) == 3
    # empty frontier degrades to plain sampling of the original space
    assert space.refine([], 5, seed=1) == space.sample(5, seed=1)


def test_refine_accepts_candidates_and_feeds_points_search():
    graph = _vecadd()
    grid = u280_grid()
    space = SearchSpace(utils=(0.7, 0.8))
    res = explore_design_space(graph, grid, space=space, sim_firings=40)
    pts = space.refine(res.frontier, 6, seed=2)
    assert pts
    zoom = explore_design_space(graph, grid, points=pts, sim_firings=40)
    assert zoom.space_size == len(pts)
    assert zoom.best.fmax >= 0.95 * res.best.fmax


# ---------------------------------------------------------------------------
# FIFO-sizing area credit (fmax surrogate feedback)
# ---------------------------------------------------------------------------


def test_buffer_bits_charge_is_monotone():
    """More buffered bits -> more slot load -> never a higher fmax."""
    graph = _vecadd()
    grid = u280_grid()
    pl = {n: (0, 0) if i % 2 else (1, 0)
          for i, n in enumerate(graph.tasks)}
    small = {s.name: 1e3 for s in graph.streams}
    big = {s.name: 4e6 for s in graph.streams}
    r0 = analyze_timing(graph, grid, pl)
    r_small = analyze_timing(graph, grid, pl, buffer_bits=small)
    r_big = analyze_timing(graph, grid, pl, buffer_bits=big)
    assert r_small.fmax_mhz <= r0.fmax_mhz
    assert r_big.fmax_mhz < r_small.fmax_mhz
    # the charge lands in slot utilization, not just the fmax number
    assert max(r_big.slot_util.values()) > max(r0.slot_util.values())


def test_sized_candidate_never_scores_below_uniform_twin():
    """Regression (ROADMAP item): crediting reclaimed FIFO bits back into
    slot utilization must never score the sized design below its
    uniform-headroom twin."""
    graph = _vecadd()
    grid = u280_grid()
    model = PhysicalModel()
    res = explore_design_space(graph, grid,
                               space=SearchSpace(utils=(0.7, 0.8)),
                               model=model, sim_firings=60, fifo_sizing=True)
    assert res.frontier
    for c in res.frontier:
        assert c.sized_capacity is not None
        assert c.sized_report is not None and c.uniform_report is not None
        assert c.fifo_savings_bits >= 0
        assert c.sized_report.fmax_mhz >= c.uniform_report.fmax_mhz


# ---------------------------------------------------------------------------
# cross-design benchmark batching (fmax suite acceptance)
# ---------------------------------------------------------------------------


def _load_bench(name):
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks", f"{name}.py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_fmax_suite_fast_subset_is_one_padded_sweep():
    """Acceptance: the fast subset's whole simulation phase is one padded
    array-sweep — >= 3x fewer Python-level simulation invocations than the
    one-batch-per-design path it replaces, with zero event-engine runs."""
    fs = _load_bench("fmax_suite")
    from repro.fpga import benchmarks as B
    entries = [fs.prepare(name, board, graph)
               for name, board, graph in B.autobridge_suite()
               if name in fs.FAST_SUBSET]
    assert len(entries) >= 6          # 6 designs, some on both boards
    sim = fs.score_all(entries, 60, "numpy")
    assert sim["counts"]["numpy"] == 1
    assert sim["counts"]["event"] == 0
    assert sim["backends"] == ["numpy-padded"]
    # the replaced path issued one simulate_batch per design
    assert sim["invocations"] * 3 <= len(entries)
    rows = [fs.finish(e, 60) for e in entries]
    for r in rows:
        assert r["opt_mhz"] > 0, r
        assert r["sim_deadlock"] is False
        assert r["throughput_preserved"] is True
        assert r["backend_used"] == "numpy-padded"


def test_fmax_suite_jax_backend_matches_numpy_rows():
    """Acceptance for the jitted backend at the suite level: scoring the
    same designs with ``backend="jax"`` reproduces the NumPy rows exactly
    (everything but wall time and the engine label), runs exactly one
    jitted sweep with zero numpy/event/fallback ticks, and records the
    jit compile-cache plus the measured NumPy-vs-jax speedup."""
    fs = _load_bench("fmax_suite")
    from repro.fpga import benchmarks as B
    names = {"stencil_x2", "bucket_sort"}

    def entries():
        return [fs.prepare(name, board, graph)
                for name, board, graph in B.autobridge_suite()
                if name in names]

    e_np = entries()
    fs.score_all(e_np, 60, "numpy")
    rows_np = [fs.finish(e, 60) for e in e_np]
    e_jx = entries()
    sim = fs.score_all(e_jx, 60, "jax")
    rows_jx = [fs.finish(e, 60) for e in e_jx]
    assert sim["counts"]["jax"] == 1
    assert sim["counts"]["numpy"] == sim["counts"]["event"] == 0
    assert sim["counts"]["fallback"] == 0
    assert sim["backends"] == ["jax-padded"]
    assert sim["jit_cache"]["compiles"] + sim["jit_cache"]["hits"] >= 1
    assert sim["speedup"]["numpy_wall_s"] > 0       # measured, not asserted
    assert sim["speedup"]["jax_wall_s"] > 0
    for a, b in zip(rows_np, rows_jx):
        assert b["backend_used"] == "jax-padded"
        for k in a:
            if k not in ("wall_s", "backend_used"):
                assert a[k] == b[k], k


def test_check_regression_jax_gate(tmp_path):
    """check_jax_backend: a --backend jax run gated against the fresh
    NumPy JSON — row-exact identity, jax counter > 0, zero silent
    fallbacks, jit_cache presence."""
    import json
    cr = _load_bench("check_regression")

    def doc(counts, *, backend, engine, opt=300.0, cycles=100, jit=False):
        d = {
            "suite": "fmax_suite",
            "subset": ["stencil_x2"],
            "backend": backend,
            "rows": [{"name": "d", "board": "u280", "opt_mhz": opt,
                      "cycles_opt": cycles, "backend_used": engine}],
            "summary": {"opt_avg_mhz": opt, "sim_deadlocks": 0,
                        "throughput_violations": 0},
            "sim": {"counts": counts, "invocations": sum(counts.values()),
                    "analysis": {"analyzed": 7, "doomed": 0, "skipped": 0,
                                 "infeasible": 0}},
        }
        if jit:
            d["sim"]["jit_cache"] = {"compiles": 1, "hits": 0}
        return d

    def write(name, d):
        p = tmp_path / name
        p.write_text(json.dumps(d))
        return str(p)

    NP = {"event": 0, "cycle": 0, "numpy": 1, "jax": 0, "fallback": 0}
    JX = {"event": 0, "cycle": 0, "numpy": 0, "jax": 1, "fallback": 0}
    base = write("base.json", doc(NP, backend="numpy", engine="numpy-padded"))
    good = write("good.json",
                 doc(JX, backend="jax", engine="jax-padded", jit=True))
    assert cr.main([good, base]) == 0
    # bit-exact identity: even an fmax IMPROVEMENT fails...
    up = write("up.json", doc(JX, backend="jax", engine="jax-padded",
                              opt=301.0, jit=True))
    assert cr.main([up, base]) == 1
    # ...as does any cycle-count divergence
    cyc = write("cyc.json", doc(JX, backend="jax", engine="jax-padded",
                                cycles=101, jit=True))
    assert cr.main([cyc, base]) == 1
    # silent degrade out of the jitted path: numpy ran under backend=jax
    mixed = dict(JX, numpy=1)
    deg = write("deg.json", doc(mixed, backend="jax", engine="jax-padded",
                                jit=True))
    assert cr.main([deg, base]) == 1
    # the sweep never ran at all
    off = write("off.json", doc(dict(JX, jax=0), backend="jax",
                                engine="jax-padded", jit=True))
    assert cr.main([off, base]) == 1
    # a fallback tick fails
    fb = write("fb.json", doc(dict(JX, fallback=1), backend="jax",
                              engine="jax-padded", jit=True))
    assert cr.main([fb, base]) == 1
    # a row scored on the wrong engine fails
    eng = write("eng.json", doc(JX, backend="jax", engine="numpy-padded",
                                jit=True))
    assert cr.main([eng, base]) == 1
    # missing jit_cache counters fail
    nojit = write("nojit.json", doc(JX, backend="jax", engine="jax-padded"))
    assert cr.main([nojit, base]) == 1


def test_check_regression_flags_event_fallback(tmp_path):
    """The CI gate fails a fast-subset run whose simulation phase degraded
    to per-job event simulation."""
    import json
    cr = _load_bench("check_regression")

    def doc(counts):
        return {
            "suite": "fmax_suite",
            "subset": ["stencil_x2"],
            "rows": [{"name": "d", "board": "u280", "opt_mhz": 300.0}],
            "summary": {
                "opt_avg_mhz": 300.0,
                "sim_deadlocks": 0,
                "throughput_violations": 0,
            },
            "sim": {"counts": counts,
                    "invocations": sum(counts.values()),
                    "analysis": {"analyzed": 7, "doomed": 0,
                                 "skipped": 0, "infeasible": 0}},
        }

    def write(name, d):
        p = tmp_path / name
        p.write_text(json.dumps(d))
        return str(p)

    base = write("base.json", doc({"event": 0, "cycle": 0, "numpy": 1}))
    good = write("good.json", doc({"event": 0, "cycle": 0, "numpy": 1}))
    fell = write("fell.json", doc({"event": 12, "cycle": 0, "numpy": 0}))
    multi = write("multi.json", doc({"event": 0, "cycle": 0, "numpy": 5}))
    assert cr.main([good, base]) == 0
    assert cr.main([fell, base]) == 1
    assert cr.main([multi, base]) == 1
    # vacuous pass closed: a sim phase that never ran is also a failure
    none_ran = write("none.json", doc({"event": 0, "cycle": 0, "numpy": 0}))
    assert cr.main([none_ran, base]) == 1
    cycled = write("cycled.json", doc({"event": 0, "cycle": 3, "numpy": 1}))
    assert cr.main([cycled, base]) == 1

    # the throughput suite shares the gate (no subset key: always applies)
    def tdoc(counts):
        return {
            "suite": "throughput",
            "rows": [{"name": "d", "cycles_tapa": 100}],
            "sim": {"counts": counts,
                    "invocations": sum(counts.values()),
                    "analysis": {"analyzed": 5, "doomed": 0,
                                 "skipped": 0, "infeasible": 0}},
        }

    tbase = write("tbase.json", tdoc({"event": 0, "cycle": 0, "numpy": 1}))
    tfell = write("tfell.json", tdoc({"event": 5, "cycle": 0, "numpy": 0}))
    assert cr.main([tbase, tbase]) == 0
    assert cr.main([tfell, tbase]) == 1


def test_check_regression_chaos_gate(tmp_path):
    """check_chaos + check_store: the chaos drill's resumed JSON gated
    against the clean converged run — row identity plus proof the faults
    fired (injected counters), bit (retries/rebuilds/store quarantines)
    and never escalated (zero pool quarantines / merge conflicts)."""
    import json
    cr = _load_bench("check_regression")

    COUNTS = {"event": 0, "cycle": 0, "numpy": 3, "jax": 0, "fallback": 0}

    def doc(*, chaos=True, opt=300.0, resumed_rounds=1, kill_rc=-9,
            retried=4, timed_out=1, rebuilds=2, pool_quar=0,
            store_quar=3, conflicts=0, merge_conflicts=0, injected=None):
        if injected is None:
            injected = {"worker_crash": 5, "worker_hang": 2,
                        "torn_write": 3, "parent_kill": 0}
        row = {"name": "d", "board": "u280", "opt_mhz": opt, "util": 0.8,
               "frontier": 2, "hypervolume": 1.5, "rounds_run": 3,
               "points_evaluated": 18, "cycles_opt": 100, "cycles_base": 90,
               "resumed_rounds": resumed_rounds if chaos else 0}
        d = {
            "suite": "fmax_suite", "converge": True, "subset": ["d"],
            "rows": [row],
            "summary": {"opt_avg_mhz": opt, "sim_deadlocks": 0,
                        "throughput_violations": 0},
            "sim": {
                "counts": COUNTS, "points_evaluated": 18,
                "floorplan": {"solved": 9, "cache_hits": 12,
                              "merge_conflicts": 0, "ilp_bipartitions": 20},
                "pool": {"jobs": 2, "dispatched": 9, "merged": 9,
                         "worker_solves": 9, "worker_infeasible": 0,
                         "retried": retried if chaos else 0,
                         "timed_out": timed_out if chaos else 0,
                         "quarantined": pool_quar,
                         "pool_rebuilds": rebuilds if chaos else 0},
                "analysis": {"analyzed": 7, "doomed": 0, "skipped": 0,
                             "infeasible": 0},
                "store": {"writes": 9, "disk_hits": 0, "disk_misses": 18,
                          "quarantined": store_quar if chaos else 0,
                          "evictions": 0, "conflicts": conflicts,
                          "entries": 9},
                "faults": {
                    "plan": ({"seed": 7, "worker_crash": 0.25}
                             if chaos else None),
                    "injected": (injected if chaos else
                                 dict.fromkeys(injected, 0)),
                    "observed": {
                        "retried": retried if chaos else 0,
                        "timed_out": timed_out if chaos else 0,
                        "quarantined": pool_quar,
                        "pool_rebuilds": rebuilds if chaos else 0,
                        "store_quarantined": store_quar if chaos else 0,
                        "merge_conflicts": merge_conflicts},
                },
            },
        }
        if chaos:
            d["chaos"] = {"killed_runs": 1, "kill_returncode": kill_rc,
                          "resumed": resumed_rounds > 0,
                          "resumed_designs": ["d"] if resumed_rounds else [],
                          "fault_plan": {"seed": 7}}
        return d

    def write(name, d):
        p = tmp_path / name
        p.write_text(json.dumps(d))
        return str(p)

    base = write("base.json", doc(chaos=False))
    good = write("good.json", doc())
    assert cr.main([good, base]) == 0
    # identity is exact: a row divergence fails even when it improves
    assert cr.main([write("row.json", doc(opt=301.0)), base]) == 1
    # the kill must have been delivered by signal
    assert cr.main([write("rc.json", doc(kill_rc=0)), base]) == 1
    # a drill where nothing resumed proves nothing
    assert cr.main([write("nores.json", doc(resumed_rounds=0)), base]) == 1
    # fault machinery must show activity...
    assert cr.main([write("noretry.json", doc(retried=0)), base]) == 1
    assert cr.main([write("norebuild.json", doc(rebuilds=0)), base]) == 1
    assert cr.main([write("noquar.json", doc(store_quar=0)), base]) == 1
    vac = dict.fromkeys(("worker_crash", "worker_hang", "torn_write"), 0)
    assert cr.main([write("noinj.json", doc(injected=vac)), base]) == 1
    # ...but never escalate to frontier-moving verdicts
    assert cr.main([write("poison.json", doc(pool_quar=1)), base]) == 1
    assert cr.main([write("mc.json", doc(merge_conflicts=1)), base]) == 1


def test_check_regression_store_gate(tmp_path):
    """check_store on a healthy (non-chaos) converged --store run: write
    conflicts always fail; quarantined entries fail without chaos."""
    import json
    cr = _load_bench("check_regression")

    def doc(*, conflicts=0, quarantined=0, converge=True):
        d = {
            "suite": "fmax_suite", "converge": converge, "subset": ["d"],
            "rows": [{"name": "d", "board": "u280", "opt_mhz": 300.0}],
            "summary": {"opt_avg_mhz": 300.0, "sim_deadlocks": 0,
                        "throughput_violations": 0},
            "sim": {
                "counts": {"event": 0, "cycle": 0, "numpy": 3, "jax": 0,
                           "fallback": 0},
                "points_evaluated": 18,
                "floorplan": {"solved": 9, "cache_hits": 12,
                              "merge_conflicts": 0, "ilp_bipartitions": 20},
                "analysis": {"analyzed": 7, "doomed": 0, "skipped": 0,
                             "infeasible": 0},
                "store": {"writes": 9, "disk_hits": 0, "disk_misses": 18,
                          "quarantined": quarantined, "evictions": 0,
                          "conflicts": conflicts, "entries": 9},
            },
        }
        return d

    def write(name, d):
        p = tmp_path / name
        p.write_text(json.dumps(d))
        return str(p)

    # the converged run gates against the NON-converged fmax baseline
    base = write("base.json", doc(converge=False))
    assert cr.main([write("ok.json", doc()), base]) == 0
    assert cr.main([write("conf.json", doc(conflicts=1)), base]) == 1
    assert cr.main([write("quar.json", doc(quarantined=2)), base]) == 1


def test_fmax_suite_converged_parallel_surrogate_fast_subset(tmp_path):
    """Tier-1 coverage for the converged ``--jobs N --proposer surrogate``
    path (previously nightly-only): on a fast-subset design the parallel
    surrogate run must reproduce the sequential surrogate run's rows
    bit-identically (the pool only relocates deterministic ILP solves),
    record the worker dispatch/merge counters, and stamp the proposer and
    jobs into the JSON sim block the CI gate reads."""
    import json

    fs = _load_bench("fmax_suite")
    kw = dict(verbose=False, sim_firings=60, subset=("stencil_x2",),
              proposer="surrogate")
    seq_rows = fs.main_converged(**kw)
    par_path = tmp_path / "par.json"
    par_rows = fs.main_converged(jobs=2, json_path=str(par_path), **kw)
    assert seq_rows and len(seq_rows) == len(par_rows)
    identity = ("opt_mhz", "util", "frontier", "hypervolume",
                "rounds_run", "points_evaluated", "cycles_opt",
                "cycles_base")
    for a, b in zip(seq_rows, par_rows):
        for field in identity:
            assert a[field] == b[field], (a["name"], field)
        assert b["converged"] in (True, False)
    doc = json.loads(par_path.read_text())
    assert doc["converge"] is True
    sim = doc["sim"]
    assert sim["proposer"] == "surrogate"
    assert sim["pool"]["jobs"] == 2
    assert sim["pool"]["merged"] == sim["pool"]["dispatched"]
    assert sim["counts"]["fallback"] == 0
    assert sim["floorplan"]["cache_hits"] > 0
