"""JAX persistent compilation cache placement.

Call :func:`enable_compile_cache` from a program's ``main()`` (never at
import). If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
nothing is set here; otherwise the cache goes to ``<checkout>/.jax_cache``.
The path is fixed on purpose: JAX passes the cache directory into the
compile options (XLA's autotune-cache path), so the directory is part of
every cache key, and a temp, per-process or per-run path never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
