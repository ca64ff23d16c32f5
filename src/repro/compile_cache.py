"""Placement of JAX's persistent compilation cache for the entry points.

Entry points (`chip_smoke.py`, `examples/genfv_cifar.py`,
`benchmarks/run.py`) call `use_compile_cache()` before their first
compile; importing `repro` never does, so tests compile as before.

* `JAX_COMPILATION_CACHE_DIR` set: JAX already reads it; nothing is set
  in code and the cache stays there.
* otherwise: the cache goes to `<checkout>/.jax_cache`. The path is fixed
  (no temp name, pid or timestamp) so a later process finds what an
  earlier one wrote; a fleet bucket of the full-width model takes minutes
  to compile for a TPU, and a warm cache turns that into a load.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: src/repro/compile_cache.py -> <checkout>/.jax_cache (listed in .gitignore)
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
