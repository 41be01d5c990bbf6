"""JAX's persistent compilation cache, kept at one stable place.

Every entry point (the chip benchmark through ``bench/harness.py``,
``chip_smoke.py``, ``benchmarks/run.py``, the examples through
``examples/_bootstrap.py``, ``launch/train.py``) calls
:func:`enable_compile_cache` before its first compile. The directory is
``$JAX_COMPILATION_CACHE_DIR`` when that is set, and otherwise
``<checkout>/.jax_cache`` (git-ignored). The path is part of the cache's
key, so it is never built from a temp name, a pid or the time. Tests do
not turn the cache on.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/cache.py -> the checkout root
_CHECKOUT = Path(__file__).resolve().parents[3]


def compile_cache_dir() -> str:
    """The cache directory: ``$JAX_COMPILATION_CACHE_DIR`` if set, else
    the fixed ``.jax_cache`` directory of this checkout."""
    return os.environ.get(ENV_VAR) or str(_CHECKOUT / ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at
    :func:`compile_cache_dir` and return that directory."""
    import jax

    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
