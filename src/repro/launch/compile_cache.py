"""JAX's persistent compilation cache, kept at one fixed place.

The cache directory is part of what a later process must find again, so
it never depends on a temporary name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: in-checkout default (listed in .gitignore)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    no other directory is set here; otherwise the cache is the
    checkout's ``.jax_cache``.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
