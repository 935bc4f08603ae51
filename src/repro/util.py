"""Small shared helpers."""

from __future__ import annotations

import os
from pathlib import Path

# <repo>/src/repro/util.py -> <repo>
REPO_ROOT = Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache (JAX reads it
    itself) and no other directory is configured. Otherwise the cache
    lives at the fixed ``<repo>/.jax_cache``: the directory is part of
    what makes an entry findable again, so it never depends on a temp
    dir, a pid or a time. Every compile is cached, however quick, so a
    second run of the same program reloads instead of recompiling.
    Call before the first compile."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def pow2_bucket(n: int, lo: int = 1) -> int:
    """Smallest power of two ≥ n, floored at ``lo`` (itself a power of
    two). Used to bucket dynamic batch/prompt sizes so jit caches see
    O(log n) shapes instead of one per size."""
    b = lo
    while b < n:
        b *= 2
    return b
