"""JAX persistent compilation cache for the entry points.

Every entry point's ``main`` calls :func:`enable_compile_cache` first; nothing
calls it at import.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads
that directory itself and nothing is set here.  Otherwise the cache lives at
a fixed ``.jax_cache/`` in the checkout root: the directory is part of the
cache key, so a name that changed per run or per process would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
