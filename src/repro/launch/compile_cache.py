"""JAX's persistent compilation cache for the repo's chip entry points.

``chip_smoke.py`` and ``benchmarks/run.py`` call :func:`enable_compile_cache`
before their first compile. The cache's path is part of every entry's key,
so it is a fixed directory: ``JAX_COMPILATION_CACHE_DIR`` when the
environment sets it (JAX reads that variable itself), otherwise
``<repo>/.jax_cache``. Tests never turn the cache on.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["REPO_CACHE_DIR", "enable_compile_cache"]

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses it and this
    sets nothing else; otherwise the cache goes to ``<repo>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
