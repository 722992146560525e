"""Test-suite bootstrap: make the tests directory importable so modules can
use the `_propcheck` hypothesis-compat shim regardless of pytest import
mode, and make `src/` importable even without PYTHONPATH=src."""
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(os.path.dirname(_HERE), "src")
for p in (_HERE, _SRC):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402  (after the sys.path bootstrap above)


@pytest.fixture(autouse=True)
def _obs_isolation():
    """Per-test observability isolation: snapshot the process-wide metrics
    registry and trace state before each test and restore them after, so
    tests never see counters or spans leaked by an earlier test and no
    longer need ad-hoc ``reset_*_counts()`` preambles."""
    from repro.obs import metrics, trace

    snap = metrics.snapshot()
    was_enabled = trace.enabled()
    saved_events = trace.drain()
    try:
        yield
    finally:
        metrics.restore(snap)
        trace.clear()
        trace.attach("")  # drop any worker-token base a test installed
        if was_enabled:
            trace.enable()
        else:
            trace.disable()
        trace.absorb(saved_events)
