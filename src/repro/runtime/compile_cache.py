"""JAX's persistent compilation cache at one fixed place per checkout.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, ``launch/train.py``,
``launch/serve.py``) call :func:`enable_compile_cache` before their first
compile; library code never does. The cache directory is part of the
cache's key, so it is a fixed path inside the checkout, never one built
from a temporary name, a process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed. Otherwise the cache goes to ``.jax_cache/`` at
    the root of the checkout.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
