"""Where JAX keeps its persistent compilation cache.

Called by entry points (``repro.launch.gwas.main``, ``chip_smoke.py``,
``benchmarks/run.py``), never at import.  A cache only hits when its
directory is stable between runs, so the path is fixed: the one
``JAX_COMPILATION_CACHE_DIR`` names (JAX reads that variable itself, and
nothing is set here), else ``.jax_cache`` at the root of the checkout.
"""
from __future__ import annotations

import os

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
