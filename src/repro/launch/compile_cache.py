"""JAX's persistent compilation cache, placed from outside or in the checkout.

A full-width model compiles one prefill program per prompt bucket plus the
round and tail programs, so a cold run spends much of its time compiling.
The entry points (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks/engine_validation.py``) call :func:`use_compile_cache` before
their first compile:

* with ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads it itself and nothing
  here sets another directory;
* otherwise the cache lives in ``.jax_cache/`` at the root of the checkout
  (listed in ``.gitignore``).  The path is fixed — it is part of the cache
  key, so a directory named from a pid, a time or a temporary name would
  never be hit again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
