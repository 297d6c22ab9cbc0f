"""JAX's persistent compilation cache at a fixed path, keyed with metadata.

The cache key includes the cache's path, so a directory that moves between
runs never hits.  By default the key also leaves out each instruction's
metadata, so a program that differs from a cached one only in its named
scopes (``repro.obs``) would load the cached executable with the old names,
and a profile of it would carry them.  Keying with metadata makes a cached
executable carry the scopes of the code that asked for it; the source files
in that metadata are named relative to the checkout, so a checkout that
moves still hits.  Importing this module sets that policy for a JAX
imported later (a benchmark imports it first); entry points call
:func:`use_compile_cache` once, before their first compile.
"""
from __future__ import annotations

import os
import re
from pathlib import Path

_ROOT = Path(__file__).resolve().parents[3]

#: ``<checkout>/.jax_cache`` (listed in .gitignore)
CACHE_DIR = str(_ROOT / ".jax_cache")

_METADATA_IN_KEY = "jax_compilation_cache_include_metadata_in_key"
_SOURCE_REGEX = "jax_hlo_source_file_canonicalization_regex"
#: removed from each source file name in the metadata: the checkout's root
SOURCE_PREFIX = "^" + re.escape(str(_ROOT) + os.sep)

os.environ.setdefault(_METADATA_IN_KEY.upper(), "true")
os.environ.setdefault(_SOURCE_REGEX.upper(), SOURCE_PREFIX)


def use_compile_cache() -> str:
    """Keep compiled programs across runs, keyed with their metadata;
    returns the cache directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    the directory is left alone.  Otherwise the cache sits at
    :data:`CACHE_DIR`."""
    import jax
    jax.config.update(_METADATA_IN_KEY, True)
    jax.config.update(_SOURCE_REGEX, SOURCE_PREFIX)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
