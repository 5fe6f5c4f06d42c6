"""JAX's persistent compilation cache, placed for entry-point scripts.

:func:`use_compile_cache` is called first by the command-line entry
points (``chip_smoke.py``, ``benchmarks/run.py``), before anything
compiles; library code and tests never call it.

* ``JAX_COMPILATION_CACHE_DIR`` set: jax already reads it, and the
  directory is left to it.
* unset: the cache goes to ``<checkout>/.jax_cache``, a fixed path, so a
  second run from the same checkout finds the first run's executables.

The minimum compile time for an entry is lowered to zero: a whole-graph
MLPerf-Tiny program compiles in well under jax's default of one second,
and every one of them is worth keeping.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["use_compile_cache"]

_CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(_CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
