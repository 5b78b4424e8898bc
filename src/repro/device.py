"""Process-level JAX set-up shared by the entry points (``chip_smoke.py`` and
the ``benchmarks/`` sweeps). Nothing here runs at import time; each entry
point calls :func:`enable_compile_cache` once, before its first JAX work.
"""
from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache, fixed by this file's location: the cache key
# includes nothing that moves between runs of the same checkout
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this leaves that setting alone; otherwise the cache goes to
    ``<checkout>/.jax_cache``. The scheduler's programs each compile in
    well under a second, so the minimum compile time worth caching drops
    to zero — at JAX's default nothing of this repo would be cached."""
    import jax
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cache_dir
