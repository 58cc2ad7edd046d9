"""Persistent XLA compilation cache, set the same way by every entry point.

A cold TPU run spends much of its time compiling; JAX's persistent cache
keeps compiled programs on disk between processes. The cache key includes
the directory, so the directory must not move between runs:

  * ``JAX_COMPILATION_CACHE_DIR`` set -> JAX reads it itself; nothing is
    set here.
  * otherwise -> ``<repo>/.jax_cache`` (listed in ``.gitignore``).

Entry points (``chip_smoke.py``, ``repro.launch.train``, ``examples/*``,
``benchmarks/run.py``) call :func:`enable` once at start-up. Tests do not:
a compile made for a described (not attached) TPU is written to the cache
but can never be read back, and the next read warns.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
