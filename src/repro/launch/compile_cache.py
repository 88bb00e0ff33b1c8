"""Where JAX keeps its persistent compilation cache.

Every entry point (``launch/serve.py``, ``benchmarks/run.py``,
``chip_smoke.py``) calls :func:`enable_compile_cache` once before it
compiles. ``JAX_COMPILATION_CACHE_DIR``, when set, places the cache;
otherwise it lives at ``<repo>/.jax_cache`` (ignored by git). The path is
part of the cache key, so it is fixed rather than per run.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at its directory; returns the path."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
