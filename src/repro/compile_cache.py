"""Where JAX keeps its persistent compilation cache.

Every entry point (``chip_smoke.py``, ``repro.launch.serve``,
``repro.launch.train``, ``benchmarks/fmax_suite.py``) calls ``configure()``
before its first compile.  The place comes from outside:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it, and nothing is
  set in code.
* unset: one fixed directory inside the checkout, ``.jax_cache/`` (listed
  in ``.gitignore``).  A cache entry's key includes nothing of the path,
  but a directory that moved from run to run would never be found again,
  so the path never comes from a temporary name, a process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the checkout root: src/repro/compile_cache.py -> ../..
CHECKOUT = Path(__file__).resolve().parents[2]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def configure() -> str:
    """Point JAX's persistent compilation cache at its directory (see the
    module docstring) and return that directory."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
