"""What a cell is, read from files by name.

A cell (one entry of ``workloads`` in ``BENCHMARK.json``) joins

* ``bench/configs/<config>.json``: the model's published sizes, the
  program's config they pin, and the reference family that computes it;
* ``bench/traffic/<traffic>.json``: the parameters of the traffic mix;
* ``bench/cells/<workload>.json``: the limits of the correctness check;
* the end-to-end and per-layer metrics that list the cell (or list none).

Nothing here knows any particular cell: a new cell is new files and new
entries in ``BENCHMARK.json``.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class Spec(dict):
    """A configuration file's contents, hashable so a jitted function can
    take it as a static argument."""

    def __hash__(self):
        return hash(json.dumps(self, sort_keys=True))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Spec
    traffic: dict
    limits: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def _read(path: Path) -> dict:
    return json.loads(path.read_text())


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load(workload: str, benchmark: Path = ROOT / "BENCHMARK.json") -> Cell:
    """The cell named ``workload``; a name not in ``BENCHMARK.json`` is an
    error."""
    bench = _read(benchmark)
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise SystemExit(f"no workload {workload!r} in {benchmark}")
    config = next(c for c in bench["configs"] if c["name"] == entry["config"])
    return Cell(
        name=workload, chips=entry["chips"],
        config=Spec(_read(ROOT / config["file"])),
        traffic=_read(BENCH / "traffic" / f"{entry['traffic']}.json"),
        limits=_read(BENCH / "cells" / f"{workload}.json")["limits"],
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)])
