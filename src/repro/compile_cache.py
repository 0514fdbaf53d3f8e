"""JAX's persistent compilation cache, pointed at one stable place.

The engine compiles one program per (policy, scenario, chunk, mesh) for
each of its core factories, so a cold process spends much of its start-up
compiling.  ``enable_compile_cache`` lets a later process in the same
checkout load those programs instead:

* where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
  module sets no other directory;
* otherwise the cache lives at ``<checkout>/.jax_cache`` — a fixed path,
  because the path is part of what makes an entry found again.

Either way the minimum compile time for an entry to be written drops to 0,
so the engine's many sub-second programs are cached too.  Call it once,
before the first compile (``chip_smoke.py`` and ``benchmarks/run.py`` do).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
