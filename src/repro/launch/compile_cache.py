"""JAX's persistent compilation cache, placed once per process.

Launchers call :func:`enable_compile_cache` before their first compile;
nothing calls it at import. ``JAX_COMPILATION_CACHE_DIR``, when set, is
read by JAX itself and this module sets no other directory. Otherwise the
cache lives at ``<checkout>/.jax_cache``: a fixed path, because the path is
part of what a later run must match to hit the cache.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
