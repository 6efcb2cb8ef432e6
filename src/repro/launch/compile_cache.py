"""JAX's persistent compilation cache, placed from outside or at one fixed
path inside the checkout.

Entry points (``chip_smoke.py``, ``repro.launch.serve``, ``benchmarks/run.py``)
call :func:`enable_compile_cache` once at start-up; nothing calls it at
import.  If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
this sets nothing.  Otherwise the cache lives in ``.jax_cache/`` at the
checkout root (git-ignored).  The path is part of every cache key, so it is
fixed: never a temp, pid- or time-derived directory.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    placed = os.environ.get(ENV)
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
