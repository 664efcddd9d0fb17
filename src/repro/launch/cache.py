"""Where jax keeps its persistent compilation cache.

A run on a fresh machine compiles every device program; the persistent
cache lets a later process (or a later run that finds the directory
again) load them instead.  The path is part of what makes a cache hit,
so it is fixed: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets
it (jax reads it into ``jax_compilation_cache_dir`` at import), else
``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import pathlib

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

# <checkout>/src/repro/launch/cache.py -> <checkout>/.jax_cache
CHECKOUT_CACHE_DIR = str(pathlib.Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Point jax's persistent compilation cache at its directory and
    return it: the configured ``jax_compilation_cache_dir`` is kept as
    it is; when none is configured, :data:`CHECKOUT_CACHE_DIR` is set.
    Call before the first compile."""
    path = jax.config.jax_compilation_cache_dir
    if path is None:
        path = CHECKOUT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
