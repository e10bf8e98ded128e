"""JAX's persistent compilation cache for the program's entry points.

The GP chunk programs are the largest compiles in the repo, one per
(config, shapes, chunk, bucket), and a process starts with none of
them.  The cache keeps them across processes.  Its path is part of
every entry's key, so it is one fixed directory and never a temporary
name: ``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it itself), and
otherwise ``.jax_cache`` at the root of the checkout (git-ignored).

A benchmark that times a compile wraps it in :func:`cold_compiles`, so
the number is a compile and never a cache load.
"""
from __future__ import annotations

import contextlib
import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CHECKOUT_CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


@contextlib.contextmanager
def cold_compiles():
    """Compiles inside neither read nor write the persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()     # JAX decides once per process
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()
