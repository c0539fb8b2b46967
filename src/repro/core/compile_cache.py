"""JAX's persistent compilation cache, at one fixed place per checkout.

Every entry point (``chip_smoke.py``, the ``repro.launch`` CLIs,
``benchmarks/run.py``) calls :func:`enable_compile_cache` before its first
compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it
and that directory is the cache; nothing else is set.  Otherwise the cache
lives in ``<repo>/.jax_cache`` (listed in ``.gitignore``).  The path never
holds a temporary name, a pid or a time: it is part of the cache key, so a
directory that moves never hits.
"""

from __future__ import annotations

import os

import jax

__all__ = ["enable_compile_cache", "REPO_CACHE_DIR"]

_REPO_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "..")
)
REPO_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on for every compile; returns its path."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or REPO_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    # the kernels compile in about a second each: cache those too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
