"""Where JAX keeps its persistent compilation cache.

A program that compiles the same shapes run after run (the federation tick
programs, the training scan, the serving buckets) pays the compile once per
machine instead of once per process when JAX's persistent cache is on. The
cache only hits when it is found at the same path every time, so the path is
never derived from a temp name, a PID or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the default cache directory: ``.jax_cache`` at the repository root
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, places the cache from outside:
    JAX reads it itself, and this leaves it alone. Otherwise the cache goes
    to ``DEFAULT_CACHE_DIR``."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return jax.config.jax_compilation_cache_dir
