"""Persistent JAX compilation cache for the repository's entry points.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
:func:`enable_compile_cache` once at start-up, before their first compile;
importing ``repro`` never does.  The cache's location is part of what JAX
keys it by, so it must be a fixed path:

- if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing
  is set here;
- otherwise the cache goes to ``<repo root>/.jax_cache`` (git-ignored).
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
