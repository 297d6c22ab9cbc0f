"""JAX's persistent compilation cache at a fixed path.

The cache key includes the cache's path, so a directory that moves between
runs never hits.  Entry points call :func:`use_compile_cache` once, before
their first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

#: ``<checkout>/.jax_cache`` (listed in .gitignore)
CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def use_compile_cache() -> str:
    """Keep compiled programs across runs; returns the cache directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is configured here.  Otherwise the cache sits at
    :data:`CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
