"""JAX's persistent compilation cache, at a path a caller can place.

Entry points call :func:`enable_compile_cache` once, before their first
compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads that
directory itself and this module sets nothing.  Otherwise the cache lives
at ``<checkout>/.jax_cache``: a fixed path, because the path is part of
what makes a later process find the entries again.
"""

from __future__ import annotations

import os

import jax

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
