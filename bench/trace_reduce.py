"""From a profiler trace to the numbers the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
``Trace``: the device operations and program executions of every chip, and
the host's spans on the thread that ran the benchmark.  ``Trace`` also
round-trips through JSON, so a small recorded trace can be kept for the
tests.  The rest are pure functions of a ``Trace``:

* ``busy``: the union of the intervals in which an operation ran on a
  chip, averaged over the chips;
* ``idle_gaps``: the gaps in that union, each labelled by the innermost
  host span that covers its middle (what the host was doing);
* ``executions``/``ops_within``: program executions by name, and the
  operations inside one;
* ``breakdown``: the device operations that took the most time and the
  longest idle gaps, ten of each.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import json
from pathlib import Path

#: lines of a device plane: one event per operation / per program run
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: host spans the benchmark opens (``jax.profiler.TraceAnnotation``)
HOST_PREFIX = "bench."


@dataclasses.dataclass
class Event:
    name: str
    start: float            # seconds, on the trace's clock
    dur: float              # seconds
    device: int = -1        # chip index; -1 on the host

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    """Events sorted by start time."""
    ops: list[Event]
    modules: list[Event]
    host: list[Event]
    n_devices: int

    def __post_init__(self):
        self._op_starts = [e.start for e in self.ops]
        self._host_starts = [e.start for e in self.host]

    def to_json(self) -> dict:
        def enc(evs):
            return [[e.name, e.start, e.dur, e.device] for e in evs]
        return {"ops": enc(self.ops), "modules": enc(self.modules),
                "host": enc(self.host), "n_devices": self.n_devices}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        def dec(rows):
            return [Event(*r) for r in rows]
        return cls(dec(d["ops"]), dec(d["modules"]), dec(d["host"]),
                   d["n_devices"])

    def window(self, lo: float, hi: float) -> "Trace":
        """The events that start in [lo, hi)."""
        def keep(evs):
            return [e for e in evs if lo <= e.start < hi]
        return Trace(keep(self.ops), keep(self.modules), keep(self.host),
                     self.n_devices)


def load(log_dir: str) -> Trace:
    """The trace ``jax.profiler`` wrote under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    ops, modules, host_lines = [], [], []
    devices = 0
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE not in lines:
                continue        # not a chip ("/device:CUSTOM:...")
            dev = devices
            devices += 1
            for name, dest in ((OPS_LINE, ops), (MODULES_LINE, modules)):
                if name in lines:
                    dest.extend(Event(e.name, e.start_ns * 1e-9,
                                      e.duration_ns * 1e-9, dev)
                                for e in lines[name].events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [Event(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                       for e in line.events]
                host_lines.append(evs)
    # the benchmark's own thread: the one that holds its spans
    host = max(host_lines, key=lambda evs: sum(
        e.name.startswith(HOST_PREFIX) for e in evs), default=[])
    for evs in (ops, modules, host):
        evs.sort(key=lambda e: e.start)
    return Trace(ops, modules, host, devices)


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------

def merge(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The union of ``intervals`` clipped to [lo, hi], sorted."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy(trace: Trace, lo: float, hi: float) -> float:
    """Seconds in [lo, hi] in which some operation ran, averaged over the
    chips."""
    n = max(trace.n_devices, 1)
    total = 0.0
    for dev in range(n):
        spans = merge(((e.start, e.end) for e in trace.ops
                       if e.device == dev), lo, hi)
        total += sum(e - s for s, e in spans)
    return total / n


def idle_gaps(trace: Trace, lo: float, hi: float, device: int = 0
              ) -> list[tuple[float, float]]:
    """(start, seconds) of each gap in chip ``device``'s busy union
    within [lo, hi]."""
    spans = merge(((e.start, e.end) for e in trace.ops
                   if e.device == device), lo, hi)
    edges = [lo] + [x for s, e in spans for x in (s, e)] + [hi]
    return [(s, e - s) for s, e in zip(edges[::2], edges[1::2]) if e > s]


def host_label(trace: Trace, t: float) -> str:
    """The innermost (latest-starting) host span that covers ``t``, or
    "" where none does."""
    i = bisect.bisect_right(trace._host_starts, t)
    for e in reversed(trace.host[:i]):
        if e.end >= t:
            return e.name
    return ""


def executions(trace: Trace, lo: float, hi: float, device: int = 0
               ) -> list[Event]:
    """Program executions on chip ``device`` that start in [lo, hi)."""
    return [e for e in trace.modules
            if e.device == device and lo <= e.start < hi]


def ops_within(trace: Trace, run: Event) -> list[Event]:
    """The operations of one program execution (same chip, inside it)."""
    i = bisect.bisect_left(trace._op_starts, run.start)
    j = bisect.bisect_right(trace._op_starts, run.end)
    return [e for e in trace.ops[i:j] if e.device == run.device
            and e.end <= run.end + 1e-9]


def breakdown(trace: Trace, lo: float, hi: float, top: int = 10) -> dict:
    """Device operations by total seconds (averaged over chips) and the
    longest idle gaps of chip 0 by what the host was doing."""
    n = max(trace.n_devices, 1)
    per_op: dict[str, float] = {}
    for e in trace.ops:
        if lo <= e.start < hi:
            per_op[e.name] = per_op.get(e.name, 0.0) + e.dur / n
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle_gaps(trace, lo, hi), key=lambda g: -g[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[host_label(trace, s + d / 2) or "(no host span)",
                           d] for s, d in gaps]}


def save(trace: Trace, path: Path) -> None:
    path.write_text(json.dumps(trace.to_json()))


def read(path: Path) -> Trace:
    return Trace.from_json(json.loads(Path(path).read_text()))
