"""JAX's persistent compilation cache for the repo's entry points.

`enable_compile_cache()` is called by the entry points (``chip_smoke.py`` and
the examples' ``main``), never at ``import repro``:

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it, and nothing else
    is set in code;
  * unset: the cache goes to ``<checkout>/.jax_cache`` (ignored by git). The
    path is fixed — it is part of the cache key, so a directory named after
    a process, a temporary name or the time would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_ENV", "enable_compile_cache", "repo_cache_dir"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def repo_cache_dir() -> Path:
    """``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/``)."""
    return Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    path = str(repo_cache_dir())
    jax.config.update("jax_compilation_cache_dir", path)
    return path
