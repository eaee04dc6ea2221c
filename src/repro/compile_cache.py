"""JAX's persistent compilation cache for this repository's entry points.

Every entry point (``chip_smoke.py``, ``repro.launch.gram_serve``,
``benchmarks/run.py``) calls :func:`use_persistent_cache` before its first
compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it
and no other directory is set here.  Otherwise the cache lives at the
fixed ``<repo>/.jax_cache`` (listed in ``.gitignore``), so the next run
of the same checkout finds it again: the path never comes from a
temporary name, a process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# src/repro/compile_cache.py -> the checkout root is parents[2]
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_persistent_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Kernel programs compile in about a second, under JAX's default
    one-second floor for caching, so every compile is cached."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
