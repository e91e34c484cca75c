"""JAX's persistent compilation cache, at a place the caller controls.

Entry points (the serve and train CLIs, ``benchmarks/run.py``,
``chip_smoke.py``) call :func:`enable_compile_cache` once at start-up;
nothing calls it while a module is imported, so tests and library users
keep JAX's default (no persistent cache).

* ``JAX_COMPILATION_CACHE_DIR`` set: that directory, and no other.
* Otherwise the fixed ``<repo>/.jax_cache`` (gitignored).  The path is
  never built from a temp name, a pid or the time: a cache only helps a
  later process that looks in the same place.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``$JAX_COMPILATION_
    CACHE_DIR`` or, when that is unset, at ``<repo>/.jax_cache``.
    Returns the directory in use."""
    path = os.environ.get(ENV_VAR) or str(REPO_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
