"""Where JAX keeps its persistent compilation cache.

A compiled program is found again only under the same cache path, so
the path is fixed: ``$JAX_COMPILATION_CACHE_DIR`` when the environment
sets it (JAX reads that variable itself), else ``.jax_cache`` at the
root of this checkout.  Entry points call :func:`use_compile_cache`
once, before their first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory
    and return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
