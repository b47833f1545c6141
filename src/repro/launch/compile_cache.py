"""JAX's persistent compilation cache, kept at one fixed place.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself: where it is set, that
directory is the cache and nothing here sets another. Otherwise the cache
is ``<checkout>/.jax_cache`` (git-ignored). The path is fixed because it is
part of what a later run must find again: a temporary or per-process
directory never hits. Entry points call ``enable_compile_cache`` from
``main``, never at import.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
