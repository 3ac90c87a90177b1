"""Where JAX's persistent compile cache lives, for every entry point.

The directory is part of the cache key, so it must not move between
runs: either the operator places it (``JAX_COMPILATION_CACHE_DIR``,
which JAX reads by itself — nothing is set in code then) or it is the
fixed ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import os

import jax

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def setup_compile_cache() -> str:
    """Point JAX at the compile cache; returns the directory in use."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return path
