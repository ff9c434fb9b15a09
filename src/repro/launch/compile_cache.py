"""JAX's persistent compilation cache, placed from outside the program."""

from __future__ import annotations

import os
import pathlib

import jax

#: the fixed default: ``<repo>/.jax_cache`` (this file is
#: ``<repo>/src/repro/launch/compile_cache.py``)
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps the cache
    there and nothing is set here.  Otherwise the cache is the fixed
    :data:`REPO_CACHE_DIR`: a run finds only what earlier runs wrote to the
    same directory, so the path never depends on a temporary name, a
    process or the time.  Call it from a program's ``main``, never on
    import.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
