"""Persistent XLA compilation cache for the entry points.

``serve.main``, ``chip_smoke.py`` and ``benchmarks/run.py`` call
:func:`enable_compile_cache` once at start-up (never at import).  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this sets
nothing.  Otherwise the cache lives at ``<checkout>/.jax_cache``: a fixed
path, because the directory is part of what a later run must find again.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
