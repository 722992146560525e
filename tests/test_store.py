"""The disk-backed floorplan store (PR-8 tentpole, ``repro.search.store``).

Covers: persist-and-reopen with zero re-solves, infeasibility verdicts
surviving the process, torn/corrupt/misfiled blob quarantine, the
content address being stable across processes (frozenset order and
string-hash randomization), bounded stores evicting oldest-first,
first-writer-wins with conflict *detection* (not silent drops), stale
temp-file cleanup, and — stateful-machine-tested — interleaved writers
with deterministic kill-mid-write fault injection reproducing an
in-memory reference model after reopen.
"""
import os
import pickle
import subprocess
import sys
import tempfile

import pytest

from _propcheck import RuleBasedStateMachine, machine_st, rule, run_state_machine

from repro.core import FloorplanCache, SlotGrid, TaskGraphBuilder, autobridge
from repro.core.ilp import InfeasibleError
from repro.search import (
    DiskFloorplanStore,
    SearchJournal,
    key_digest,
    store_counts,
)
from repro.search import faults
from repro.search.store import _read_blob, _write_blob


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


# ---------------------------------------------------------------------------
# blob format
# ---------------------------------------------------------------------------


def test_blob_roundtrip_and_torn_detection(tmp_path):
    p = tmp_path / "x.fp"
    _write_blob(p, b"payload bytes")
    assert _read_blob(p) == b"payload bytes"
    # torn tail: checksum must fail, not return a prefix
    raw = p.read_bytes()
    p.write_bytes(raw[:-3])
    assert _read_blob(p) is None
    # flipped bit inside the payload
    _write_blob(p, b"payload bytes")
    raw = bytearray(p.read_bytes())
    raw[-1] ^= 0x01
    p.write_bytes(bytes(raw))
    assert _read_blob(p) is None
    # wrong magic
    p.write_bytes(b"XXXX" + raw[4:])
    assert _read_blob(p) is None


# ---------------------------------------------------------------------------
# DiskFloorplanStore
# ---------------------------------------------------------------------------


def test_reopened_store_serves_solves_without_resolving(tmp_path):
    g = _chain_graph()
    first = DiskFloorplanStore(tmp_path)
    autobridge(g, GRID, cache=first)
    assert first.disk_entries() >= 1

    second = DiskFloorplanStore(tmp_path)
    plan = autobridge(g, GRID, cache=second)
    # every lookup fell through memory -> disk: no ILP solve ran
    assert second.misses == 0
    assert second.disk_hits >= 1
    ref = autobridge(g, GRID, cache=FloorplanCache())
    assert plan.floorplan.placement == ref.floorplan.placement
    assert plan.depth == ref.depth


def test_infeasible_verdict_survives_the_process(tmp_path):
    g = _chain_graph()
    first = DiskFloorplanStore(tmp_path)
    with pytest.raises(InfeasibleError):
        # util=0.02 caps every slot below one task
        autobridge(g, GRID, max_util=0.02, cache=first)

    second = DiskFloorplanStore(tmp_path)
    with pytest.raises(InfeasibleError):
        autobridge(g, GRID, max_util=0.02, cache=second)
    assert second.misses == 0          # the verdict came from disk


def test_torn_entry_quarantined_on_reopen(tmp_path):
    first = DiskFloorplanStore(tmp_path)
    autobridge(_chain_graph(), GRID, cache=first)
    (entry,) = list(first.entries_dir.glob("*.fp"))
    entry.write_bytes(entry.read_bytes()[:10])

    second = DiskFloorplanStore(tmp_path)     # verify_on_open scrubs
    assert second.quarantined == 1
    assert store_counts()["quarantined"] == 1
    assert second.disk_entries() == 0
    assert list(second.quarantine_dir.glob("*.corrupt"))
    # the miss re-solves and re-persists; the store heals
    autobridge(_chain_graph(), GRID, cache=second)
    assert second.disk_entries() == 1


def test_misfiled_entry_quarantined_not_served(tmp_path):
    first = DiskFloorplanStore(tmp_path)
    first.record_infeasible(("k", 1), "nope")
    (entry,) = list(first.entries_dir.glob("*.fp"))
    # internally-consistent blob filed under the wrong content address
    wrong = entry.with_name("0" * 64 + ".fp")
    entry.rename(wrong)
    second = DiskFloorplanStore(tmp_path)
    assert second.quarantined == 1
    assert second.cached_error(("k", 1)) is None


def test_stale_tmp_files_removed_on_open(tmp_path):
    store = DiskFloorplanStore(tmp_path)
    stale = store.entries_dir / ("a" * 64 + ".fp.123.tmp")
    stale.write_bytes(b"half a write")
    reopened = DiskFloorplanStore(tmp_path)
    assert not list(reopened.entries_dir.glob("*.tmp"))


def test_key_digest_canonicalizes_frozensets():
    a = key_digest((frozenset({frozenset({"x", "y"}), frozenset({"z"})}),))
    b = key_digest((frozenset({frozenset({"z"}), frozenset({"y", "x"})}),))
    assert a == b


def test_key_digest_stable_across_processes():
    key = ("sig", frozenset({frozenset({"x", "y"}), frozenset({"z"})}),
           0.8, 0, 22, 8, 6.0)
    here = key_digest(key)
    code = ("from repro.search.store import key_digest\n"
            "print(key_digest(('sig', frozenset({frozenset({'x', 'y'}), "
            "frozenset({'z'})}), 0.8, 0, 22, 8, 6.0)))\n")
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ)
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("PYTHONHASHSEED", None)    # fresh random string hashing
    for _ in range(2):
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, check=True)
        assert out.stdout.strip() == here


def test_bounded_store_evicts_oldest(tmp_path):
    store = DiskFloorplanStore(tmp_path, max_entries=2)
    for i in range(4):
        store.record_infeasible(("k", i), f"v{i}")
        os.utime(store._entry_path(("k", i)), (i + 1, i + 1))
    assert store.disk_entries() == 2
    assert store_counts()["evictions"] == 2
    # the newest entries survived
    kept = {p.name for p in store.entries_dir.glob("*.fp")}
    assert kept == {key_digest(("k", 2)) + ".fp", key_digest(("k", 3)) + ".fp"}


def test_concurrent_writer_conflict_detected_first_writer_kept(tmp_path):
    a = DiskFloorplanStore(tmp_path)
    b = DiskFloorplanStore(tmp_path)
    a.record_infeasible(("k",), "verdict A")
    # the race window: b's lookup missed before a's os.replace committed,
    # so b proceeds to persist its own (disagreeing) value — the store
    # must detect the disagreement instead of dropping it silently
    assert b._put(("k",), ("err", "verdict B"))
    assert store_counts()["conflicts"] == 1
    fresh = DiskFloorplanStore(tmp_path)
    assert fresh.cached_error(("k",)) == "verdict A"   # first writer wins


def test_agreeing_concurrent_writers_are_not_conflicts(tmp_path):
    a = DiskFloorplanStore(tmp_path)
    b = DiskFloorplanStore(tmp_path)
    a.record_infeasible(("k",), "same verdict")
    assert b._put(("k",), ("err", "same verdict"))     # same race, same value
    assert store_counts()["conflicts"] == 0


# ---------------------------------------------------------------------------
# SearchJournal
# ---------------------------------------------------------------------------


def test_journal_save_load_roundtrip(tmp_path):
    j = SearchJournal(tmp_path, config={"a": 1})
    assert j.load_latest() is None
    j.save_round(0, {"x": 1, "hypervolume": 0.5})
    j.save_round(1, {"x": 2, "hypervolume": 0.7})
    state = j.load_latest()
    assert state["round"] == 1 and state["x"] == 2
    assert j.rounds_on_disk() == 2
    lines = j.journal_path.read_text().splitlines()
    assert len(lines) == 2 and '"round": 1' in lines[1]


def test_journal_torn_newest_falls_back_to_previous_round(tmp_path):
    j = SearchJournal(tmp_path, config={"a": 1})
    j.save_round(0, {"x": 1})
    j.save_round(1, {"x": 2})
    newest = j._state_path(1)
    newest.write_bytes(newest.read_bytes()[:7])
    state = SearchJournal(tmp_path, config={"a": 1}).load_latest()
    assert state["round"] == 0 and state["x"] == 1
    assert not newest.exists()         # quarantined, not retried forever
    assert newest.with_suffix(".pkl.corrupt").exists()


def test_journal_refuses_mismatched_config(tmp_path):
    SearchJournal(tmp_path, config={"rounds": 3})
    with pytest.raises(ValueError, match="config mismatch"):
        SearchJournal(tmp_path, config={"rounds": 4})
    # same config re-attaches fine
    SearchJournal(tmp_path, config={"rounds": 3})


def test_journal_garbage_state_blob_is_quarantined(tmp_path):
    j = SearchJournal(tmp_path, config={})
    path = j._state_path(0)
    _write_blob(path, pickle.dumps(["not", "a", "dict"]))
    assert j.load_latest() is None
    assert path.with_suffix(".pkl.corrupt").exists()


# ---------------------------------------------------------------------------
# stateful property: interleaved writers + kill-mid-write ≡ reference model
# ---------------------------------------------------------------------------


class DiskStoreMachine(RuleBasedStateMachine):
    """Two writer processes (modelled as two store instances over one
    root) interleave first-writer-wins entry writes while a seeded fault
    plan tears a deterministic subset of them mid-write (the kill-mid-
    write drill: an atomic-rename crash leaves nothing, the injected tear
    leaves a detectable corpse).  A writer may 'die' at any point and
    reopen with empty memory.  The reference model predicts durability
    per key straight from the fault plan — ``FaultPlan.decide`` is pure —
    and a fresh store opened at the end must agree with it exactly."""

    PLAN = faults.FaultPlan(seed=11, torn_write=0.5)

    def __init__(self):
        self._tmp = tempfile.TemporaryDirectory(prefix="repro-storeprop-")
        self.root = self._tmp.name
        self.writers = [DiskFloorplanStore(self.root),
                        DiskFloorplanStore(self.root)]
        self.model: dict[tuple, str] = {}       # key -> first-written value
        self.wrote: list[set[tuple]] = [set(), set()]

    def _durable(self, key) -> bool:
        return not self.PLAN.decide("torn_write", key_digest(key))

    @rule(w=machine_st.integers(0, 1), i=machine_st.integers(0, 11))
    def put(self, w, i):
        key, value = ("k", i), f"verdict for {i}"
        with faults.install(self.PLAN):
            self.writers[w].record_infeasible(key, value)
        self.model.setdefault(key, value)
        self.wrote[w].add(key)

    @rule(w=machine_st.integers(0, 1), i=machine_st.integers(0, 11))
    def lookup(self, w, i):
        key = ("k", i)
        got = self.writers[w].cached_error(key)
        if key in self.wrote[w] or (key in self.model and self._durable(key)):
            assert got == self.model[key]
        else:
            assert got is None

    @rule(w=machine_st.integers(0, 1))
    def kill_and_reopen(self, w):
        # a killed writer loses its memory tier; disk is all that remains
        self.writers[w] = DiskFloorplanStore(self.root)
        self.wrote[w] = set()

    def finalize(self):
        fresh = DiskFloorplanStore(self.root)
        for key, value in self.model.items():
            got = fresh.cached_error(key)
            if self._durable(key):
                assert got == value, (key, "durable write lost")
            else:
                assert got is None, (key, "torn write served")
        # determinism must make disagreement impossible
        assert store_counts()["conflicts"] == 0


def test_disk_store_interleaved_writers_property():
    run_state_machine(DiskStoreMachine, steps=14, max_examples=6)
