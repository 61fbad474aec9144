"""Where JAX keeps its persistent compilation cache for this repository.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``,
``repro.launch.serve``) call :func:`enable_compile_cache` once, before they
compile anything; importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["DEFAULT_CACHE_DIR", "REPO_ROOT", "enable_compile_cache"]

# The checkout root (this file is <root>/src/repro/compile_cache.py).
REPO_ROOT = Path(__file__).resolve().parents[2]
DEFAULT_CACHE_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``$JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting: it is
    left to JAX and nothing is set here.  Otherwise the cache goes to
    ``<checkout>/.jax_cache``.  The path is fixed because it is part of
    what a later process must find again: a directory named after a temp
    file, a pid or the time never hits.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
