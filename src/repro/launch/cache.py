"""Persistent XLA compilation cache for runs on the chip."""
from __future__ import annotations

import os

import jax


def enable_compile_cache(root: str) -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    no other directory is set here. Otherwise the cache lives at the fixed
    path ``<root>/.jax_cache``: the directory is part of what a later run
    must find again, so it is never built from a temporary name, a process
    id or the time. Call before the first compile, never at import.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(os.path.abspath(root), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
