"""Where the entry points keep JAX's persistent compilation cache.

The launchers (``repro.launch.serve``, ``repro.launch.train``) and
``chip_smoke.py`` call :func:`enable_compile_cache` before they compile
anything; library code and tests never do. The directory is part of the
cache's key, so it is fixed: ``$JAX_COMPILATION_CACHE_DIR`` when that is
set (JAX reads it itself), otherwise ``.jax_cache`` at the root of the
checkout (git-ignored).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
