"""JAX's persistent compilation cache, placed for the entry points.

Entry points (``chip_smoke.py``, ``launch/serve.py``, ``launch/train.py``,
``benchmarks/run.py``, ``examples/*``) call :func:`enable_compile_cache`
before they compile anything; importing ``repro`` sets no cache.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# fixed, inside the checkout: the cache only hits when the path is stable
CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache: JAX reads it
    itself and nothing is set in code.  Otherwise the cache lives in
    ``.jax_cache/`` at the root of the checkout."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
