"""JAX persistent compilation cache location, set once at program start.

Entry points (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks/run.py``) call ``setup_compile_cache()`` before their first
compile; library code never does, so importing ``repro`` changes no JAX
configuration.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["DEFAULT_CACHE_DIR", "setup_compile_cache"]

# Fixed path: the directory is part of the cache key, so one that moved
# between runs (a temporary name, a pid, a timestamp) would never hit.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it
    into its configuration and nothing is changed here. Otherwise the
    cache goes to ``<repo>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
