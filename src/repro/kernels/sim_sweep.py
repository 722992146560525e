"""JAX-jitted padded batch simulator sweep (``simulate_batch(backend="jax")``).

A ``jax.jit``-compiled port of the NumPy padded array-sweep
(``repro.core.simulate._simulate_batch_numpy``): one ``lax.while_loop``
advances every job of a padded (V, T*, S*) batch by one synchronous cycle
per iteration, with the mutable state buffers (push-history ring,
pop/push counts, firing counters, II windows) donated to the compiled
computation.  The semantics are a statement-for-statement transcription
of the NumPy engine — the property harness in
``tests/test_simulate_event.py`` asserts bit-identical ``SimResult``s
(cycles, fired, deadlocked, steps) on randomized mixed batches — so the
NumPy backend remains the bit-exact oracle, exactly as the event engine
is the oracle for NumPy.

Compilation caching
-------------------
The sweep's shapes are *bucketed*: V, T*, S* and the ring depth H are
rounded up to the next power of two before tracing, and the extra rows,
columns and ring slots are inert phantom padding (the same masking
discipline ``repro.kernels.padded_batch`` already applies to ragged
groups).  Heterogeneous search rounds whose padded layouts land in the
same bucket therefore reuse one compiled sweep instead of re-tracing per
exact shape; ``firings`` and ``max_cycles`` are traced scalars, so they
never fragment the cache.  ``sweep_cache_stats()`` exposes the
bucket-key hit/compile counters (the BENCH JSON records them under
``sim.jit_cache``).

Incidence is expressed with gathers/scatters instead of the NumPy
backend's per-group matmuls — per-job ``cons``/``prod`` index arrays
(phantom streams pointing at a sentinel task column) make the whole
update shape-generic, which is what lets one compiled sweep cover any
group structure of the same bucket.

Everything runs in int32: the public entry refuses knobs that could
overflow (``fits_int32``), and ``simulate_batch`` degrades such calls to
the NumPy backend with a counted fallback.
"""

from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..obs import metrics as _metrics
from ..obs import trace as _trace
from .padded_batch import PaddedBatch

# int32-safety threshold: keeping every knob below 2**30 leaves headroom
# for the sums the sweep forms (t + ii, pushes - pops) inside int32.
_SAFE_MAX = 1 << 30

#: compile-cache bookkeeping, keyed by the bucketed (V, T*, S*, H) shape.
#: jax's own jit cache does the actual reuse; these counters make it
#: observable to tests and the BENCH ``sim.jit_cache`` metadata.
_SEEN_SHAPES: set[tuple[int, int, int, int]] = set()
_CACHE_STATS = _metrics.group(
    "sim.jit_cache", {"compiles": 0, "hits": 0}, on_reset=_SEEN_SHAPES.clear
)


def sweep_cache_stats() -> dict[str, int]:
    """Snapshot of the jitted-sweep compile cache: ``compiles`` counts
    distinct bucketed (V, T*, S*, H) shapes traced, ``hits`` counts calls
    that reused an already-compiled sweep."""
    return dict(_CACHE_STATS)


def reset_sweep_cache_stats() -> None:
    """Zero the compile-cache counters and forget seen shapes (jax's own
    jit cache is untouched — a 're-compile' after this reset is a cache
    hit inside jax, but counts as a compile here)."""
    _CACHE_STATS.reset()


def _bucket(n: int) -> int:
    """Next power of two >= max(n, 1): the shape-bucketing that lets
    heterogeneous rounds share one compiled sweep."""
    return 1 << max(n - 1, 0).bit_length()


def fits_int32(jobs, firings: int, max_cycles: int) -> bool:
    """True when every quantity the sweep computes stays inside int32:
    cycle indices, firing counts, FIFO capacities and latencies."""
    if firings >= _SAFE_MAX or max_cycles >= _SAFE_MAX:
        return False
    for j in jobs:
        for d in (j.latency, j.extra_capacity, j.ii):
            if d and any(abs(int(x)) >= _SAFE_MAX for x in d.values()):
                return False
        if any(int(s.depth) >= _SAFE_MAX for s in j.graph.streams):
            return False
    return True


def _sweep(
    lat,
    cap,
    ii,
    task_active,
    counted,
    cons,
    prod,
    hist,
    pops,
    pushes,
    fired,
    next_free,
    firings,
    max_cycles,
):
    """One padded batch to completion.  All arrays are int32/bool; the
    state buffers (hist..next_free) are donated by the jit wrapper."""
    V, S, H = hist.shape
    T = task_active.shape[1]
    rows = jnp.arange(V, dtype=jnp.int32)[:, None]
    sent = jnp.zeros((V, 1), dtype=jnp.int32)  # sentinel gather column

    def all_done(fired):
        # phantom and detached tasks are vacuously done
        return ((fired >= firings) | ~counted).all(axis=1)

    def cond(state):
        t, active = state[0], state[2]
        return active.any() & (t < max_cycles)

    def body(state):
        t, steps, active, out_cycles, out_dead = state[:5]
        hist, pops, pushes, fired, next_free = state[5:]
        newly = active & all_done(fired)
        out_cycles = jnp.where(newly, t, out_cycles)
        out_dead = jnp.where(newly, False, out_dead)
        active = active & ~newly
        steps = steps + active.any().astype(jnp.int32)

        # firing rule against the state produced by cycles < t
        look = (t - 1 - lat) % H
        vis = jnp.take_along_axis(hist, look[:, :, None], axis=2)[:, :, 0]
        if S:
            tok_ok = vis > pops
            space_ok = (pushes - pops) < cap
            in_bad = jnp.zeros((V, T + 1), jnp.int32).at[rows, cons].add(
                (~tok_ok).astype(jnp.int32)
            )
            out_bad = jnp.zeros((V, T + 1), jnp.int32).at[rows, prod].add(
                (~space_ok).astype(jnp.int32)
            )
            in_ok = in_bad[:, :T] == 0
            out_ok = out_bad[:, :T] == 0
        else:
            in_ok = out_ok = jnp.ones((V, T), dtype=bool)

        can = (
            active[:, None]
            & task_active
            & (fired < firings)
            & (next_free <= t)
            & in_ok
            & out_ok
        )
        can_i = can.astype(jnp.int32)
        fired = fired + can_i
        next_free = jnp.where(can, t + ii, next_free)
        if S:
            can_pad = jnp.concatenate([can_i, sent], axis=1)
            pops = pops + jnp.take_along_axis(can_pad, cons, axis=1)
            pushes = pushes + jnp.take_along_axis(can_pad, prod, axis=1)
            hist = hist.at[:, :, t % H].set(pushes)

        progressed = can.any(axis=1)
        # post-update in-flight check at cycle t (matches the reference
        # engine: vis from the cycle start, pops/pushes post-update)
        if S:
            tok_missing = (pops < pushes) & (vis <= pops)
            tok_flight = tok_missing.any(axis=1)
        else:
            tok_flight = jnp.zeros(V, dtype=bool)
        ii_flight = (next_free > t).any(axis=1)
        quiet = active & ~progressed & ~tok_flight & ~ii_flight
        out_cycles = jnp.where(quiet, t + 1, out_cycles)
        out_dead = jnp.where(quiet, ~all_done(fired), out_dead)
        active = active & ~quiet
        return (
            t + 1,
            steps,
            active,
            out_cycles,
            out_dead,
            hist,
            pops,
            pushes,
            fired,
            next_free,
        )

    init = (
        jnp.int32(0),
        jnp.int32(0),
        jnp.ones(V, dtype=bool),
        jnp.zeros(V, jnp.int32) + max_cycles,
        jnp.zeros(V, dtype=bool),
        hist,
        pops,
        pushes,
        fired,
        next_free,
    )
    state = lax.while_loop(cond, body, init)
    steps, active = state[1], state[2]
    out_cycles, out_dead, fired = state[3], state[4], state[8]
    # jobs still active at the horizon: truncated (or done exactly there)
    out_cycles = jnp.where(active, max_cycles, out_cycles)
    out_dead = jnp.where(active, ~all_done(fired), out_dead)
    return out_cycles, out_dead, fired, steps


_jit_sweep = jax.jit(_sweep, donate_argnums=(7, 8, 9, 10, 11))


def _pad2(a: np.ndarray, shape: tuple[int, ...], fill) -> np.ndarray:
    out = np.full(shape, fill, dtype=a.dtype)
    out[tuple(slice(0, n) for n in a.shape)] = a
    return out


def simulate_padded_jax(pb: PaddedBatch, *, firings: int, max_cycles: int):
    """Run one canonical padded batch through the jitted sweep.

    Returns ``(cycles, dead, fired, steps)`` as host arrays/ints, sliced
    back to the batch's real (V, T*) shape — feed them to
    ``PaddedBatch.unpack``."""
    V = pb.V
    V2, T2 = _bucket(V), _bucket(pb.T)
    S2, H2 = _bucket(pb.S), _bucket(pb.H)
    key = (V2, T2, S2, H2)
    if key in _SEEN_SHAPES:
        _CACHE_STATS["hits"] += 1
        stage = "jit.execute"
    else:
        _SEEN_SHAPES.add(key)
        _CACHE_STATS["compiles"] += 1
        stage = "jit.compile"

    i32 = np.int32
    lat = _pad2(pb.lat.astype(i32), (V2, S2), 0)
    cap = _pad2(pb.cap.astype(i32), (V2, S2), 0)
    ii = _pad2(pb.ii.astype(i32), (V2, T2), 1)
    task_active = _pad2(pb.task_active, (V2, T2), False)
    counted = _pad2(pb.counted, (V2, T2), False)
    # remap the layout's sentinel task column (pb.T) to the bucketed
    # sentinel (T2), then pad the extra stream columns with it too
    cons = np.where(pb.stream_active, pb.cons, T2).astype(i32)
    prod = np.where(pb.stream_active, pb.prod, T2).astype(i32)
    cons = _pad2(cons, (V2, S2), T2)
    prod = _pad2(prod, (V2, S2), T2)

    with _trace.span(stage, shape=str(key), batch=V), warnings.catch_warnings():
        # donation is for accelerator backends; on CPU jax ignores it and
        # warns, which would otherwise spam every sweep
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable"
        )
        out_cycles, out_dead, fired, steps = _jit_sweep(
            jnp.asarray(lat),
            jnp.asarray(cap),
            jnp.asarray(ii),
            jnp.asarray(task_active),
            jnp.asarray(counted),
            jnp.asarray(cons),
            jnp.asarray(prod),
            jnp.zeros((V2, S2, H2), jnp.int32),
            jnp.zeros((V2, S2), jnp.int32),
            jnp.zeros((V2, S2), jnp.int32),
            jnp.zeros((V2, T2), jnp.int32),
            jnp.zeros((V2, T2), jnp.int32),
            jnp.int32(firings),
            jnp.int32(max_cycles),
        )
        # host transfer inside the span: jax dispatch is async, so the
        # sweep's real wall time lands in these asarray calls
        out = (
            np.asarray(out_cycles)[:V],
            np.asarray(out_dead)[:V],
            np.asarray(fired)[:V],
            int(steps),
        )
    return out
