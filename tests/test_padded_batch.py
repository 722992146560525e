"""Property tests for the canonical padded batch layout
(``repro.kernels.padded_batch``) in isolation.

The two invariants every array backend relies on:

* **phantom tasks never fire** — padding columns are masked out of the
  firing rule (``task_active`` False) and vacuously done in the
  termination/deadlock checks (``counted`` False);
* **phantom streams never stall** — padding streams attach to the
  sentinel task column, so no real task's readiness can ever depend on
  them.

Structural properties check the masks/sentinels directly on randomized
heterogeneous batches; behavioral properties compare each job's padded
result against its own unpadded batch-of-one run (equal cycles prove no
phantom stream ever stalled a real task) and, under jax, inspect the
sweep's padded ``fired`` array itself (phantom columns must stay 0).
"""

import random

import numpy as np
from _propcheck import given, settings, strategies as st

from repro.core import SimJob, simulate_batch
from repro.corpus import random_graph as _random_graph
from repro.kernels.padded_batch import build_padded_batch

def _mixed_jobs(seed: int) -> list:
    """2-6 jobs over independently random topologies (cycles, detached
    tasks, zero-capacity FIFOs, random latency/headroom/II knobs)."""
    rng = random.Random(seed)
    jobs = []
    for _ in range(rng.randint(2, 6)):
        g = _random_graph(rng, allow_cycle=True)
        lat = {s.name: rng.randint(0, 4) for s in g.streams}
        extra = {s.name: rng.choice([0, 0, 2, 2 * lat[s.name]]) for s in g.streams}
        ii = {n: rng.randint(1, 4) for n in g.tasks}
        jobs.append(SimJob(g, latency=lat, extra_capacity=extra, ii=ii))
    return jobs


@settings(max_examples=40, deadline=None)
@given(st.integers(0, 99_999))
def test_layout_masks_keep_padding_inert(seed):
    """Structural invariants: groups tile the rows contiguously, ``perm``
    is a permutation, and every padding column is inert — masked tasks
    with identity II, sentinel-attached streams with zero knobs."""
    jobs = _mixed_jobs(seed)
    pb = build_padded_batch(jobs)
    assert sorted(pb.perm) == list(range(pb.V))
    spans = [(g.r0, g.r1) for g in pb.groups]
    assert spans[0][0] == 0 and spans[-1][1] == pb.V
    assert all(a[1] == b[0] for a, b in zip(spans, spans[1:]))
    assert pb.T == max(g.T for g in pb.groups)
    assert pb.S == max(g.S for g in pb.groups)
    assert pb.H >= int(pb.lat.max(initial=0)) + 2
    # counted (termination-relevant) is a subset of the real-task mask
    assert (pb.counted <= pb.task_active).all()
    for g in pb.groups:
        rows = slice(g.r0, g.r1)
        T, S = g.T, g.S
        # phantom tasks: out of the firing rule, vacuously done, II=1
        assert pb.task_active[rows, :T].all()
        assert not pb.task_active[rows, T:].any()
        assert not pb.counted[rows, T:].any()
        assert (pb.ii[rows, T:] == 1).all()
        # phantom streams: attached to the sentinel column, zero knobs
        assert pb.stream_active[rows, :S].all()
        assert not pb.stream_active[rows, S:].any()
        assert (pb.cons[rows, S:] == pb.T).all()
        assert (pb.prod[rows, S:] == pb.T).all()
        assert (pb.lat[rows, S:] == 0).all()
        assert (pb.cap[rows, S:] == 0).all()
        # real streams always attach below the group's own task count
        if S:
            assert (pb.cons[rows, :S] < T).all()
            assert (pb.prod[rows, :S] < T).all()
        # incidence matrices agree with the flat producer/consumer maps
        for si in range(S):
            assert g.a_in[si].sum() == 1 and g.a_in[si, g.cons[si]] == 1
            assert g.a_out[si].sum() == 1 and g.a_out[si, g.prod[si]] == 1
        assert (g.indeg == g.a_in.sum(axis=0)).all()
        assert (g.outdeg == g.a_out.sum(axis=0)).all()


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 99_999))
def test_phantom_streams_never_stall_padded_equals_solo(seed):
    """Behavioral: each job's padded result equals its own batch-of-one
    run (where no cross-job padding exists at all) — so phantom streams
    introduced by batching can never have stalled a real task."""
    jobs = _mixed_jobs(seed)
    padded = simulate_batch(jobs, firings=20, backend="numpy")
    for job, got in zip(jobs, padded):
        solo = simulate_batch([job], firings=20, backend="numpy")[0]
        assert got.cycles == solo.cycles
        assert got.fired == solo.fired
        assert got.deadlocked == solo.deadlocked


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 99_999))
def test_phantom_tasks_never_fire(seed):
    """Behavioral, on the sweep's own padded state: every phantom task
    column — group padding AND the jit bucketing's extra columns — ends
    the sweep with a zero firing count."""
    from repro.kernels.sim_sweep import simulate_padded_jax

    jobs = _mixed_jobs(seed)
    pb = build_padded_batch(jobs)
    _, _, fired, _ = simulate_padded_jax(pb, firings=20, max_cycles=11_280)
    fired = np.asarray(fired)
    T = pb.T
    assert (fired[:, :T][~pb.task_active] == 0).all()
    assert (fired[:, T:] == 0).all()


def test_unpack_restores_original_job_order():
    """``unpack`` inverts the grouping permutation: padded row ``v`` lands
    at original index ``perm[v]``, and each result's fired dict names
    exactly its own graph's tasks (phantom columns never leak out)."""
    jobs = _mixed_jobs(123)
    pb = build_padded_batch(jobs)
    cycles = np.arange(pb.V)
    dead = np.zeros(pb.V, dtype=bool)
    fired = np.zeros((pb.V, pb.T), dtype=np.int64)
    out = pb.unpack(cycles, dead, fired, 7, "test")
    assert all(r is not None for r in out)
    for v in range(pb.V):
        assert out[pb.perm[v]].cycles == v
    for job, res in zip(jobs, out):
        assert set(res.fired) == set(job.graph.tasks)
        assert res.steps == 7 and res.engine == "test"
