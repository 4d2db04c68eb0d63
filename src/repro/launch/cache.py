"""Where the entry points keep JAX's persistent compilation cache.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` on its own; where it is set, that
directory is the cache and nothing here names another.  Otherwise an
entry point keeps the cache at one fixed path inside its checkout,
``<root>/.jax_cache`` (git-ignored): the path is part of what a cached
program is found by, so it is never built from a temp name, a pid or a
time.  Importing ``repro`` never calls this, so tests compile without a
persistent cache.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def use_compile_cache(root) -> str:
    """Point JAX's compile cache at ``$JAX_COMPILATION_CACHE_DIR``, else at
    ``<root>/.jax_cache``; returns the directory in use."""
    cache_dir = os.environ.get(ENV_VAR)
    if not cache_dir:
        cache_dir = str(Path(root).resolve() / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir
