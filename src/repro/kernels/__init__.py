"""Compute hot-spot kernels.

Two families live here:

* Pallas/``jax`` model kernels (``flash_attention``, ``mamba2_scan``,
  ``rwkv6_scan``, ``moe_gmm``, ``burst_gather``) routed through
  ``repro.kernels.ops`` with reference implementations in
  ``repro.kernels.ref`` — import those submodules directly.
* The padded batch simulator sweep: ``repro.kernels.padded_batch``
  builds the canonical padded (V, T*, S*) layout both ``simulate_batch``
  array backends consume, and ``repro.kernels.sim_sweep`` is the
  ``jax.jit``-compiled sweep behind ``simulate_batch(backend="jax")``.

Exports resolve lazily (PEP 562): importing ``repro.kernels`` — or
``repro.core``, which pulls it in for ``simulate_batch`` — does not import
jax; touching a ``sim_sweep`` name does.
"""
from __future__ import annotations

_PADDED_EXPORTS = ("PaddedBatch", "PaddedGroup", "build_padded_batch")
_SWEEP_EXPORTS = ("fits_int32", "reset_sweep_cache_stats",
                  "simulate_padded_jax", "sweep_cache_stats")

__all__ = [*_PADDED_EXPORTS, *_SWEEP_EXPORTS]


def __getattr__(name):
    if name in _PADDED_EXPORTS:
        from repro.kernels import padded_batch

        return getattr(padded_batch, name)
    if name in _SWEEP_EXPORTS:
        from repro.kernels import sim_sweep

        return getattr(sim_sweep, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(set(globals()) | set(__all__))
