"""Persistent XLA compilation cache for the launchers and ``chip_smoke.py``.

A cold compile of a full-width 28-layer step costs seconds, and every chip
run starts from nothing.  The cache key includes the cache's own path, so
it has to sit at one fixed place to ever hit: where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this module
sets no directory; otherwise the cache lives at ``<checkout>/.jax_cache``
(git-ignored).  Either way every compiled program is kept, not only those
that took JAX's default one second to compile: a decode step compiles in
under a second and is compiled again on every run.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/compile_cache.py -> the checkout root
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
