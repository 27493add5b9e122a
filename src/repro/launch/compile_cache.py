"""Where JAX keeps its persistent compilation cache.

Compiling is most of a cold start of a full-width model, so the entry
points keep compiled programs across processes.  The cache key includes
the cache path, so the path must not move between runs: it is either the
directory ``JAX_COMPILATION_CACHE_DIR`` names (JAX reads that variable
itself, and nothing is set here) or the fixed ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: src/repro/launch/compile_cache.py -> the checkout root
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent cache; returns the directory in use."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
