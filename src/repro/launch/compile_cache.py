"""JAX's persistent compilation cache, for entry points only.

A cold run compiles every prefill and decode program of a full-width model;
the persistent cache lets later processes reuse them.  The cache directory
is part of the cache key, so it must not move between runs: it is either
the directory ``JAX_COMPILATION_CACHE_DIR`` names (JAX reads the variable
itself) or the fixed ``.jax_cache/`` at the repository root.  Importing
``repro`` never turns the cache on; entry points call
:func:`enable_compile_cache` before their first compile.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
