"""JAX's persistent compilation cache for the repo's entry points.

A protocol run builds many compiled sessions (SSL, server fits, k-means,
SDPA, the few-shot gate, serving); the persistent cache lets a later
process on the same machine load them instead of compiling again. Only
entry points call :func:`enable_compile_cache`, from their ``main()`` —
importing the library or running the test suite leaves the cache off.

The rule: if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
this module sets nothing. Otherwise the cache lives at a fixed
``.jax_cache/`` in the checkout (git-ignored). The path is part of what a
cache hit needs, so it is never a temp, pid or time-based directory.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
