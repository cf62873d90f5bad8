"""JAX's persistent compilation cache, at one fixed place.

Entry points (``chip_smoke.py``, ``repro.launch.serve``, ``benchmarks.run``)
call :func:`enable` once at start-up, never at import. Where
``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and this module
sets nothing. Otherwise the cache lives in ``.jax_cache/`` at the root of
the checkout: a fixed path, because the path is part of the cache key.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
