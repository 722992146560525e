"""Crash-safety of the search stack (PR-8 tentpole): the fault-injection
harness, the hardened worker pool, and checkpointed ``search_until_converged``.

Covers: ``FaultPlan`` purity and env-var propagation, pool survival of
injected worker crashes and hangs with the frontier bit-identical to a
clean run, poison-point quarantine as a cached verdict, the pool's
start method (and its results under spawn), kill-between-rounds resume
(a real SIGKILLed subprocess) reproducing the uninterrupted frontier,
completed-checkpoint replay without re-solving, torn-store-write
transparency, and the checkpoint config fingerprint refusing foreign
arguments.
"""
import dataclasses
import json
import os
import signal
import subprocess
import sys
import textwrap

import pytest

from repro.core import (
    FloorplanCache,
    SearchSpace,
    SlotGrid,
    TaskGraphBuilder,
    floorplan_counts,
    reset_floorplan_counts,
)
from repro.search import (
    DiskFloorplanStore,
    FaultPlan,
    fault_counts,
    install_faults,
    search_until_converged,
    warm_floorplan_cache,
)
from repro.search import faults
from repro.search.pool import _mp_context
from repro.search.space import SearchPoint


def _chain_graph(n=4, width=64, lut=100):
    b = TaskGraphBuilder("chain")
    for i in range(n - 1):
        b.stream(f"s{i}", width=width)
    for i in range(n):
        b.invoke(f"K{i}", area={"LUT": lut},
                 ins=[f"s{i - 1}"] if i > 0 else [],
                 outs=[f"s{i}"] if i < n - 1 else [])
    return b.build()


GRID = SlotGrid("g", rows=2, cols=2, base_capacity={"LUT": 400},
                max_util=1.0)
SPACE = SearchSpace(seeds=(0, 1), utils=(0.8, 0.9, 1.0))
POINTS = [SearchPoint(seed=s, max_util=u)
          for s in (0, 1) for u in (0.8, 0.9, 1.0)]


def _converge_kwargs():
    return dict(space=SearchSpace(utils=(0.7, 0.85, 1.0)), rounds=3,
                points_per_round=6, sim_firings=50)


def _fingerprint(res):
    return sorted(
        (dataclasses.astuple(c.point), c.fmax, c.plan.area_overhead,
         tuple(sorted(c.plan.floorplan.placement.items())),
         c.sim.cycles if c.sim else None)
        for c in res.frontier)


# ---------------------------------------------------------------------------
# FaultPlan
# ---------------------------------------------------------------------------


def test_fault_plan_decisions_are_pure_and_seeded():
    plan = FaultPlan(seed=3, worker_crash=0.5)
    tokens = [f"t{i}" for i in range(64)]
    first = [plan.decide("worker_crash", t) for t in tokens]
    assert first == [plan.decide("worker_crash", t) for t in tokens]
    assert any(first) and not all(first)      # a rate, not a constant
    # a different seed reshuffles the selection
    other = FaultPlan(seed=4, worker_crash=0.5)
    assert first != [other.decide("worker_crash", t) for t in tokens]
    # transient by default: attempt >= attempts never faults
    victim = tokens[first.index(True)]
    assert not plan.decide("worker_crash", victim, attempt=1)
    assert FaultPlan(seed=3, worker_crash=0.5, attempts=3).decide(
        "worker_crash", victim, attempt=2)


def test_fault_plan_kill_site_matches_round_token():
    plan = FaultPlan(kill_after_round=2)
    assert plan.decide("parent_kill", "2")
    assert not plan.decide("parent_kill", "1")
    assert not FaultPlan().decide("parent_kill", "2")


def test_fault_plan_env_roundtrip():
    """A plan travels as JSON (a child's command line), and installing it
    touches this process only: nothing goes through the environment."""
    plan = FaultPlan(seed=9, torn_write=0.25, kill_after_round=1,
                     pool_timeout_s=2.0)
    assert FaultPlan.from_dict(json.loads(json.dumps(plan.as_dict()))) \
        == plan
    # unknown keys from a newer writer are ignored, not fatal
    assert FaultPlan.from_dict(
        dict(plan.as_dict(), future_knob=1)) == plan
    env = dict(os.environ)
    with install_faults(plan):
        assert faults.active_plan() == plan
        assert dict(os.environ) == env
    assert faults.active_plan() is None


def test_install_none_masks_ambient_env_plan():
    with install_faults(FaultPlan(torn_write=1.0)):
        assert faults.active_plan() is not None
        with install_faults(None):
            assert faults.active_plan() is None
        assert faults.active_plan() is not None


def test_fire_counts_and_returns_for_torn_write():
    with install_faults(FaultPlan(torn_write=1.0)):
        assert faults.fire("torn_write", "any-token") is True
    with install_faults(FaultPlan(torn_write=0.0)):
        assert faults.fire("torn_write", "any-token") is False
    assert fault_counts()["torn_write"] == 1


# ---------------------------------------------------------------------------
# hardened pool under injected faults
# ---------------------------------------------------------------------------


def _warm(plan, **kw):
    cache = DiskFloorplanStore(kw.pop("root")) if "root" in kw \
        else FloorplanCache()
    with install_faults(plan):
        stats = warm_floorplan_cache(_chain_graph(), GRID, POINTS,
                                     cache=cache, jobs=2, **kw)
    return cache, stats


def test_pool_survives_transient_worker_crashes_bit_identically():
    clean_cache = FloorplanCache()
    clean = warm_floorplan_cache(_chain_graph(), GRID, POINTS,
                                 cache=clean_cache, jobs=2)
    assert clean.retried == clean.pool_rebuilds == 0

    cache, stats = _warm(FaultPlan(seed=1, worker_crash=1.0))
    assert stats.retried >= stats.dispatched      # every point died once
    assert stats.pool_rebuilds >= 1
    assert stats.quarantined == 0                 # transient, not poison
    assert stats.merged == stats.dispatched == clean.dispatched
    assert set(cache._entries) == set(clean_cache._entries)
    for k, (kind, v) in clean_cache._entries.items():
        got_kind, got_v = cache._entries[k]
        assert got_kind == kind
        if kind == "ok":
            assert got_v.placement == v.placement


def test_pool_survives_hung_workers_via_timeout():
    cache, stats = _warm(FaultPlan(seed=2, worker_hang=1.0, hang_s=60.0),
                         timeout_s=1.0, backoff_s=0.01)
    assert stats.timed_out >= 1
    assert stats.pool_rebuilds >= 1
    assert stats.quarantined == 0
    assert stats.merged == stats.dispatched == len(POINTS)


def test_poison_point_is_quarantined_as_a_verdict():
    from repro.core import initial_floorplan_key
    # attempts high: the selected points crash on every retry
    plan = FaultPlan(seed=5, worker_crash=1.0, attempts=99)
    cache, stats = _warm(plan, crash_limit=2, backoff_s=0.01)
    assert stats.quarantined == len(POINTS)
    assert stats.merged == 0
    for pt in POINTS:
        key = initial_floorplan_key(_chain_graph(), GRID,
                                    **{f.name: getattr(pt, f.name)
                                       for f in dataclasses.fields(pt)})
        reason = cache.cached_error(key)
        assert reason is not None and reason.startswith("quarantined:")
    # the quarantine verdicts are ordinary cache entries: a re-run skips
    # the poisoned points instead of re-dispatching them
    with install_faults(plan):
        again = warm_floorplan_cache(_chain_graph(), GRID, POINTS,
                                     cache=cache, jobs=2)
    assert again.dispatched == 0


def test_injected_faults_never_change_the_converged_frontier(tmp_path):
    kw = _converge_kwargs()
    clean = search_until_converged(_chain_graph(), GRID, **kw)
    plan = FaultPlan(seed=4, worker_crash=0.5, torn_write=0.5)
    with install_faults(plan):
        chaotic = search_until_converged(
            _chain_graph(), GRID, jobs=2,
            cache=DiskFloorplanStore(tmp_path / "store"), **kw)
    assert _fingerprint(chaotic) == _fingerprint(clean)
    assert chaotic.hypervolumes == clean.hypervolumes
    assert chaotic.pool.quarantined == 0


# ---------------------------------------------------------------------------
# pool start method
# ---------------------------------------------------------------------------


def test_pool_ctx_env_override(monkeypatch):
    """No environment variable picks the start method: fork where the
    platform has it (unguarded caller scripts work), spawn elsewhere."""
    import multiprocessing

    monkeypatch.setenv("REPRO_POOL_CTX", "spawn")     # ignored
    want = ("fork" if "fork" in multiprocessing.get_all_start_methods()
            else "spawn")
    assert _mp_context().get_start_method() == want


def test_pool_solves_under_spawn_context(monkeypatch):
    """Spawned workers inherit nothing, so everything a solve needs must
    travel as arguments; the merged cache must equal the default pool's."""
    import multiprocessing

    from repro.search import pool

    monkeypatch.setattr(pool, "_mp_context",
                        lambda: multiprocessing.get_context("spawn"))
    cache = FloorplanCache()
    stats = warm_floorplan_cache(_chain_graph(), GRID, POINTS[:2],
                                 cache=cache, jobs=2)
    assert stats.merged == stats.dispatched == 2
    monkeypatch.undo()
    ref = FloorplanCache()
    warm_floorplan_cache(_chain_graph(), GRID, POINTS[:2], cache=ref, jobs=2)
    assert set(cache._entries) == set(ref._entries)


# ---------------------------------------------------------------------------
# kill-between-rounds resume
# ---------------------------------------------------------------------------

_CHILD = textwrap.dedent("""
    import json, sys
    from repro.core import SearchSpace, SlotGrid, TaskGraphBuilder
    from repro.search import (FaultPlan, install_faults,
                              search_until_converged)

    def chain(n=4, width=64, lut=100):
        b = TaskGraphBuilder("chain")
        for i in range(n - 1):
            b.stream(f"s{i}", width=width)
        for i in range(n):
            b.invoke(f"K{i}", area={"LUT": lut},
                     ins=[f"s{i - 1}"] if i > 0 else [],
                     outs=[f"s{i}"] if i < n - 1 else [])
        return b.build()

    grid = SlotGrid("g", rows=2, cols=2, base_capacity={"LUT": 400},
                    max_util=1.0)
    plan = FaultPlan.from_dict(json.loads(sys.argv[2]))
    with install_faults(plan):
        res = search_until_converged(
            chain(), grid, space=SearchSpace(utils=(0.7, 0.85, 1.0)),
            rounds=3, points_per_round=6, sim_firings=50,
            checkpoint=sys.argv[1])
    print(f"done rounds_run={res.rounds_run} "
          f"resumed_rounds={res.resumed_rounds}")
""")


def _child_env():
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ)
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    return env


def test_sigkill_between_rounds_then_resume_is_bit_identical(tmp_path):
    ckpt = tmp_path / "ckpt"
    # 1) the victim: SIGKILLs itself right after the round-0 checkpoint
    victim = subprocess.run(
        [sys.executable, "-c", _CHILD, str(ckpt),
         json.dumps(FaultPlan(kill_after_round=0).as_dict())],
        env=_child_env(),
        capture_output=True, text=True)
    assert victim.returncode == -signal.SIGKILL, victim.stderr
    assert (ckpt / "state_r0000.pkl").exists()

    # 2) resume in-process so the result object is inspectable
    resumed = search_until_converged(_chain_graph(), GRID,
                                     checkpoint=ckpt, **_converge_kwargs())
    assert resumed.resumed_rounds == 1

    # 3) the uninterrupted run it must reproduce, bit for bit
    clean = search_until_converged(_chain_graph(), GRID, **_converge_kwargs())
    assert _fingerprint(resumed) == _fingerprint(clean)
    assert resumed.hypervolumes == clean.hypervolumes
    assert resumed.rounds_run == clean.rounds_run
    assert resumed.converged == clean.converged


def test_completed_checkpoint_replays_without_solving(tmp_path):
    ckpt = tmp_path / "ckpt"
    first = search_until_converged(_chain_graph(), GRID, checkpoint=ckpt,
                                   **_converge_kwargs())
    reset_floorplan_counts()
    again = search_until_converged(_chain_graph(), GRID, checkpoint=ckpt,
                                   **_converge_kwargs())
    assert floorplan_counts()["solved"] == 0
    assert again.resumed_rounds == first.rounds_run
    assert _fingerprint(again) == _fingerprint(first)
    assert again.checkpoint_dir == os.fspath(ckpt)


def test_checkpoint_refuses_different_search_arguments(tmp_path):
    ckpt = tmp_path / "ckpt"
    search_until_converged(_chain_graph(), GRID, checkpoint=ckpt,
                           **_converge_kwargs())
    kw = _converge_kwargs() | {"rounds": 4}
    with pytest.raises(ValueError, match="config mismatch"):
        search_until_converged(_chain_graph(), GRID, checkpoint=ckpt, **kw)


def test_checkpoint_creates_disk_store_by_default(tmp_path):
    ckpt = tmp_path / "ckpt"
    res = search_until_converged(_chain_graph(), GRID, checkpoint=ckpt,
                                 **_converge_kwargs())
    assert res.checkpoint_dir == os.fspath(ckpt)
    assert DiskFloorplanStore(ckpt / "store").disk_entries() >= 1
