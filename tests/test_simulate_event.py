"""Event-driven simulator tests: bit-for-bit equivalence against the
per-cycle reference engine (randomized DAGs, reconvergent diamonds,
dependency cycles, detached tasks), batch-engine parity across the NumPy
and jax-jitted padded backends (three-way jit == numpy == event property
test, bit-identical including ``steps``), the almost-full headroom
regression, and a perf smoke proving the engine does O(firings) work
instead of O(cycles)."""
import random

import pytest
from _propcheck import given, settings, strategies as st

from repro.core import (SimJob, TaskGraphBuilder, pipeline_headroom,
                        simulate, simulate_batch)
from repro.core.graph import Stream, Task, TaskGraph
# the corpus fuzz family subsumes the old ad-hoc helper: layered DAGs
# with random fanin, zero-depth FIFOs, control streams, detached sinks,
# skip edges and (allow_cycle) occasional feedback cycles
from repro.corpus import random_graph as _random_graph

def _assert_engines_agree(g, **kw):
    ev = simulate(g, engine="event", **kw)
    cy = simulate(g, engine="cycle", **kw)
    assert (ev.cycles, ev.fired, ev.deadlocked) == \
        (cy.cycles, cy.fired, cy.deadlocked), (ev, cy)
    return ev


@settings(max_examples=40, deadline=None)
@given(st.integers(0, 99_999))
def test_equivalence_random_dags(seed):
    rng = random.Random(seed)
    g = _random_graph(rng)
    lat = {s.name: rng.randint(0, 4) for s in g.streams}
    extra = {s.name: rng.choice([0, 0, 2, 2 * lat[s.name]])
             for s in g.streams}
    ii = {n: rng.randint(1, 4) for n in g.tasks}
    _assert_engines_agree(g, firings=25, latency=lat, extra_capacity=extra,
                          ii=ii)


def _diamond():
    b = TaskGraphBuilder("d")
    for s in ("ab", "bd", "ad"):
        b.stream(s, width=32, depth=2)
    b.invoke("A", area={}, outs=["ab", "ad"])
    b.invoke("B", area={}, ins=["ab"], outs=["bd"])
    b.invoke("D", area={}, ins=["bd", "ad"])
    return b.build()


@pytest.mark.parametrize("lat,extra,ii", [
    ({}, {}, {}),                                       # plain
    ({"ab": 4, "bd": 4}, {}, {}),                       # unbalanced, tight
    ({"ab": 4, "bd": 4, "ad": 8},
     {"ab": 8, "bd": 8, "ad": 16}, {}),                 # balanced + headroom
    ({"ab": 2}, {"ab": 4}, {"A": 3, "D": 2}),           # II mix
])
def test_equivalence_reconvergent_diamond(lat, extra, ii):
    _assert_engines_agree(_diamond(), firings=120, latency=lat,
                          extra_capacity=extra, ii=ii)


def test_equivalence_dependency_cycle_deadlock():
    """A tokenless feedback cycle deadlocks immediately in both engines."""
    g = TaskGraph("cyc")
    g.add_task(Task("a"))
    g.add_task(Task("b"))
    g.add_stream(Stream(name="ab", src="a", dst="b"))
    g.add_stream(Stream(name="ba", src="b", dst="a"))
    res = _assert_engines_agree(g, firings=10)
    assert res.deadlocked
    assert res.fired == {"a": 0, "b": 0}


def test_equivalence_detached_tasks():
    b = TaskGraphBuilder("det")
    b.stream("s0", width=8)
    b.stream("s1", width=8)
    b.invoke("Src", area={}, outs=["s0", "s1"])
    b.invoke("Sink", area={}, ins=["s0"])
    b.invoke("Mon", area={}, ins=["s1"], detach=True)
    g = b.build()
    res = _assert_engines_agree(g, firings=50, latency={"s1": 3},
                                extra_capacity={"s1": 6})
    assert res.fired["Src"] == 50 and res.fired["Sink"] == 50
    assert res.fired["Mon"] <= 50   # detached: excluded from termination


# ---------------------------------------------------------------------------
# batched engine
# ---------------------------------------------------------------------------

def test_batch_numpy_matches_event():
    g = _diamond()
    rng = random.Random(3)
    jobs = [SimJob(g)]
    for _ in range(7):
        lat = {s.name: rng.randint(0, 4) for s in g.streams}
        jobs.append(SimJob(g, latency=lat,
                           extra_capacity=pipeline_headroom(lat),
                           ii={n: rng.randint(1, 3) for n in g.tasks}))
    vec = simulate_batch(jobs, firings=60, backend="numpy")
    ref = simulate_batch(jobs, firings=60, backend="event")
    assert all(r.engine == "numpy-batch" for r in vec)
    assert all(r.engine == "event" for r in ref)
    for a, b in zip(vec, ref):
        assert (a.cycles, a.fired, a.deadlocked) == \
            (b.cycles, b.fired, b.deadlocked)


def test_batch_mixed_topologies_vectorize_via_padding():
    """Mixed topologies no longer degrade to a per-job Python loop: the
    padded backend covers heterogeneous graphs in one array-sweep, with
    results identical to per-job event simulation."""
    b = TaskGraphBuilder("t2")
    b.stream("s", width=8)
    b.invoke("A", area={}, outs=["s"])
    b.invoke("B", area={}, ins=["s"])
    other = b.build()
    jobs = [SimJob(_diamond()), SimJob(other)]
    results = simulate_batch(jobs, firings=30, backend="numpy")
    assert all(r.engine == "numpy-padded" for r in results)
    ref = simulate_batch(jobs, firings=30, backend="event")
    assert all(r.engine == "event" for r in ref)
    for a, b_ in zip(results, ref):
        assert (a.cycles, a.fired, a.deadlocked) == \
            (b_.cycles, b_.fired, b_.deadlocked)


def test_batch_accepts_bare_graphs():
    out = simulate_batch([_diamond(), _diamond()], firings=40)
    assert [r.cycles for r in out] == [out[0].cycles] * 2
    assert all(not r.deadlocked for r in out)


def _random_mixed_jobs(seed: int) -> list[SimJob]:
    """2-6 jobs over independently random topologies: different task and
    stream counts, dependency cycles, detached tasks, zero-capacity FIFOs,
    random latency/headroom/II knobs."""
    rng = random.Random(seed)
    jobs = []
    for _ in range(rng.randint(2, 6)):
        g = _random_graph(rng, allow_cycle=True)
        lat = {s.name: rng.randint(0, 4) for s in g.streams}
        extra = {s.name: rng.choice([0, 0, 2, 2 * lat[s.name]])
                 for s in g.streams}
        ii = {n: rng.randint(1, 4) for n in g.tasks}
        jobs.append(SimJob(g, latency=lat, extra_capacity=extra, ii=ii))
    return jobs


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 99_999))
def test_padded_backend_equivalence_mixed_topologies(seed):
    """The padded ragged-batch backend is bit-for-bit equivalent to per-job
    event simulation on heterogeneous batches — including graphs that
    deadlock (cycles, zero-capacity FIFOs) and detached tasks."""
    jobs = _random_mixed_jobs(seed)
    vec = simulate_batch(jobs, firings=25)
    ref = simulate_batch(jobs, firings=25, backend="event")
    assert all(r.engine in ("numpy-batch", "numpy-padded", "jax-padded")
               for r in vec)
    for a, b in zip(vec, ref):
        assert (a.cycles, a.fired, a.deadlocked) == \
            (b.cycles, b.fired, b.deadlocked)


def test_backend_numpy_accepts_mixed_topologies():
    """``backend="numpy"`` used to raise on mixed batches; the padded
    backend now takes any mix (it only needs NumPy itself)."""
    b = TaskGraphBuilder("t3")
    b.stream("s", width=8)
    b.invoke("A", area={}, outs=["s"])
    b.invoke("B", area={}, ins=["s"])
    jobs = [SimJob(_diamond()), SimJob(b.build())]
    out = simulate_batch(jobs, firings=20, backend="numpy")
    assert all(r.engine == "numpy-padded" for r in out)
    # a lone job is also accepted (one group, no padding)
    solo = simulate_batch([SimJob(_diamond())], firings=20, backend="numpy")
    assert solo[0].engine == "numpy-batch"


def test_fast_subset_designs_vectorize_with_exact_results():
    """Acceptance: a batch of the full fast-subset designs (heterogeneous
    real benchmark graphs) runs through the padded numpy backend with
    results exactly equal to per-job event simulation."""
    from repro.fpga import benchmarks as B
    graphs = [B.stencil(2), B.stencil(4), B.cnn(2), B.gaussian(12),
              B.bucket_sort(), B.page_rank()]
    jobs = [SimJob(g) for g in graphs]
    vec = simulate_batch(jobs, firings=50)
    assert all(r.engine == "jax-padded" for r in vec)
    ref = [simulate(g, firings=50) for g in graphs]
    for a, b in zip(vec, ref):
        assert (a.cycles, a.fired, a.deadlocked) == \
            (b.cycles, b.fired, b.deadlocked)


def test_engine_invocation_counters():
    """The padded sweep is ONE Python-level invocation regardless of batch
    size; per-job event fallback is one per job (what the CI benchmark
    gate asserts never happens on the fast subset)."""
    from repro.core import engine_counts, reset_engine_counts
    jobs = _random_mixed_jobs(7)
    simulate_batch(jobs, firings=10)
    assert engine_counts() == {"event": 0, "cycle": 0, "numpy": 0, "jax": 1,
                               "fallback": 0}
    reset_engine_counts()
    simulate_batch(jobs, firings=10, backend="event")
    counts = engine_counts()
    assert counts["numpy"] == counts["jax"] == 0
    assert counts["event"] == len(jobs)


# ---------------------------------------------------------------------------
# jax-jitted backend (bit-exact against the NumPy oracle)
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(st.integers(0, 99_999))
def test_jax_backend_three_way_equivalence(seed):
    """jit == numpy == event on randomized mixed batches.  The jitted
    sweep's SimResults are bit-identical to the NumPy oracle's — including
    the ``steps`` counter, i.e. the very same number of sweep iterations —
    and both match per-job event simulation on cycles/fired/deadlock."""
    jobs = _random_mixed_jobs(seed)
    jx = simulate_batch(jobs, firings=25, backend="jax")
    np_ = simulate_batch(jobs, firings=25, backend="numpy")
    ev = simulate_batch(jobs, firings=25, backend="event")
    assert all(r.engine == "jax-padded" for r in jx)
    for a, b in zip(jx, np_):
        assert (a.cycles, a.fired, a.deadlocked, a.steps) == \
            (b.cycles, b.fired, b.deadlocked, b.steps)
    for a, b in zip(jx, ev):
        assert (a.cycles, a.fired, a.deadlocked) == \
            (b.cycles, b.fired, b.deadlocked)


def test_auto_promotes_to_jax():
    """backend="auto" resolves to the jitted sweep when jax imports and the
    knobs are int32-safe — with zero fallback ticks."""
    from repro.core import engine_counts
    jobs = _random_mixed_jobs(11)
    out = simulate_batch(jobs, firings=10)
    assert all(r.engine == "jax-padded" for r in out)
    counts = engine_counts()
    assert counts["jax"] == 1 and counts["numpy"] == 0
    assert counts["fallback"] == 0


def test_jax_chunking_matches_unchunked():
    """max_bytes chunking splits the jax sweep exactly like the NumPy one:
    one engine invocation per chunk, results identical to the whole-batch
    run."""
    from repro.core import engine_counts, reset_engine_counts
    jobs = _random_mixed_jobs(5)
    whole = simulate_batch(jobs, firings=15, backend="jax")
    reset_engine_counts()
    chunked = simulate_batch(jobs, firings=15, backend="jax", max_bytes=1)
    assert engine_counts()["jax"] == len(jobs)      # one sweep per chunk
    for a, b in zip(whole, chunked):
        assert (a.cycles, a.fired, a.deadlocked) == \
            (b.cycles, b.fired, b.deadlocked)


def test_jax_backend_int32_guard_raises():
    """Forcing backend="jax" past the sweep's int32 range is an error, not
    a silent degrade."""
    jobs = [SimJob(_diamond()), SimJob(_diamond())]
    with pytest.raises(ValueError, match="int32"):
        simulate_batch(jobs, firings=10, max_cycles=1 << 31, backend="jax")


def test_auto_int32_overflow_degrades_to_numpy_with_fallback_tick():
    """auto with int32-unsafe knobs degrades to the NumPy backend — but
    audibly: a warning plus an engine_counts()["fallback"] tick (what the
    CI gate asserts is zero)."""
    from repro.core import engine_counts
    jobs = [SimJob(_diamond()), SimJob(_diamond())]
    with pytest.warns(UserWarning, match="int32"):
        out = simulate_batch(jobs, firings=10, max_cycles=1 << 31)
    assert all(r.engine == "numpy-batch" for r in out)
    counts = engine_counts()
    assert counts["fallback"] == 1 and counts["numpy"] == 1
    assert counts["jax"] == 0


def test_jax_compile_cache_reuses_shapes():
    """Recompilation is keyed by the bucketed padded shape only: re-running
    the same batch with different scalar knobs (firings/max_cycles are
    traced values) must hit the cache, not recompile."""
    from repro.kernels.sim_sweep import sweep_cache_stats
    jobs = _random_mixed_jobs(3)
    simulate_batch(jobs, firings=10, backend="jax")
    first = dict(sweep_cache_stats())
    simulate_batch(jobs, firings=12, backend="jax")   # same shapes, new knobs
    second = sweep_cache_stats()
    assert first["compiles"] >= 1
    assert second["compiles"] == first["compiles"]    # no recompilation
    assert second["hits"] > first["hits"]


def test_explorer_batched_throughput_eval():
    """explore_floorplans(sim_firings=...) attaches batched simulation
    results to every feasible candidate, and best_candidate drops
    deadlocked ones."""
    from repro.core import SlotGrid, best_candidate, explore_floorplans
    b = TaskGraphBuilder("chain")
    for i in range(3):
        b.stream(f"s{i}", width=64)
    for i in range(4):
        b.invoke(f"K{i}", area={"LUT": 100},
                 ins=[f"s{i-1}"] if i > 0 else [],
                 outs=[f"s{i}"] if i < 3 else [])
    g = b.build()
    grid = SlotGrid("g", rows=2, cols=2, base_capacity={"LUT": 150},
                    max_util=1.0)
    # 0.3 is infeasible (a 100-LUT task cannot fit in 45), the rest are not
    cands = explore_floorplans(g, grid, utils=(0.3, 0.8, 1.0),
                               sim_firings=100)
    infeasible, feasible = cands[0], [c for c in cands if c.plan is not None]
    assert infeasible.plan is None and infeasible.sim is None
    assert infeasible.throughput_preserved is None
    assert feasible, "expected feasible candidates"
    for c in feasible:
        assert c.sim is not None and c.base_sim is not None
        assert not c.sim.deadlocked
        assert c.throughput_preserved is True
        # the shared baseline is simulated once for the whole sweep
        assert c.base_sim is feasible[0].base_sim
    assert best_candidate(cands).plan is not None


# ---------------------------------------------------------------------------
# almost-full headroom ownership (regression: no implicit 2*lat capacity)
# ---------------------------------------------------------------------------

def _chain2(depth):
    # raw construction: depth=0 is rejected by the builder's validation,
    # and deliberately broken FIFOs are exactly what these tests need
    g = TaskGraph("c2")
    g.add_task(Task("P"))
    g.add_task(Task("C"))
    g.add_stream(Stream(name="s", src="P", dst="C", width=8, depth=depth),
                 validate=False)
    return g


def test_tight_fifo_stalls_without_headroom():
    """A 2-deep FIFO with 4 cycles of pipeline latency cannot sustain full
    throughput: the producer stalls on almost-full.  The old simulator
    silently added 2*latency capacity and hid this."""
    g = _chain2(depth=2)
    stalled = simulate(g, firings=100, latency={"s": 4})
    healthy = simulate(g, firings=100, latency={"s": 4},
                       extra_capacity=pipeline_headroom({"s": 4}))
    assert not stalled.deadlocked and not healthy.deadlocked
    assert healthy.cycles <= 100 + 6            # fill skew only
    assert stalled.cycles > 1.8 * healthy.cycles  # real almost-full stall
    # both engines agree on the stalled schedule too
    _assert_engines_agree(g, firings=100, latency={"s": 4})


def test_zero_depth_fifo_deadlocks_under_correct_capacity():
    """depth=0 FIFO: the producer can never write.  With the old implicit
    +2*latency headroom this design simulated as healthy."""
    g = _chain2(depth=0)
    res = _assert_engines_agree(g, firings=5, latency={"s": 1})
    assert res.deadlocked
    ok = simulate(g, firings=5, latency={"s": 1}, extra_capacity={"s": 2})
    assert not ok.deadlocked


# ---------------------------------------------------------------------------
# perf smoke: event engine does O(firings) work, not O(cycles)
# ---------------------------------------------------------------------------

def test_event_engine_steps_scale_with_firings_not_cycles():
    """II=32 chain: the per-cycle engine scans every task for every one of
    ~3200 cycles; the event engine processes ~2 events per firing."""
    b = TaskGraphBuilder("hi_ii")
    b.stream("s", width=8, depth=4)
    b.invoke("A", area={}, outs=["s"])
    b.invoke("B", area={}, ins=["s"])
    g = b.build()
    ii = {"A": 32, "B": 32}
    ev = simulate(g, firings=100, ii=ii, engine="event")
    cy = simulate(g, firings=100, ii=ii, engine="cycle")
    assert (ev.cycles, ev.deadlocked) == (cy.cycles, cy.deadlocked)
    assert ev.cycles > 3000                  # high-II schedule is long...
    assert ev.steps * 10 <= ev.cycles        # ...but costs >=10x fewer steps
    assert cy.steps == cy.cycles             # reference scans every cycle
