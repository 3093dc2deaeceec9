"""Persistent XLA compilation cache, placed from outside.

One call before a program's first compile.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing is
set here; otherwise the cache lives at ``<checkout>/.jax_cache`` — a fixed
path, because the directory is part of the cache key's namespace and one
that moves (a temp dir, a pid, a time) never hits.
"""
from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every program, the sub-second ones (serving's cow_copy) too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
