"""JAX's persistent compilation cache at one fixed place.

Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it and nothing else is
set; otherwise the cache lives at ``<checkout>/.jax_cache``, a fixed path,
because the path is part of what makes an entry found again.  Entries are
written whatever their compile time, so every program of a cell is loaded
by the next run in the same checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[1] / ".jax_cache"


def enable() -> str:
    import jax

    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
