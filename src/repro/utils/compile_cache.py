"""JAX's persistent compilation cache for the command-line entry points.

A cold TPU process spends a large part of a short run compiling. The cache
keeps compiled executables on disk between processes, keyed in part by
its path, so the path must not move from run to run:

- when ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
  nothing is set here;
- otherwise the cache lives at ``<checkout>/.jax_cache`` (git-ignored).

Call :func:`enable_compile_cache` once, before the first compile, from a
program's entry point (never at import).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    path = CHECKOUT / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(path))
    return str(path)
