"""Where JAX keeps its persistent compilation cache.

Every entry point (``launch/train.py``, ``launch/serve.py``,
``benchmarks/run.py``, ``chip_smoke.py``) calls :func:`enable_compile_cache`
before it compiles anything.  The cache directory is part of the cache
key, so it never moves: ``$JAX_COMPILATION_CACHE_DIR`` when that is set
(JAX reads the variable itself, and no other directory is set here), else
``<repo root>/.jax_cache``.  Tests never call this.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
