"""Where JAX keeps its persistent compilation cache.

The cache key includes the directory, so the directory must not move
between runs: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it
(JAX reads that variable itself), otherwise ``.jax_cache`` at the root of
this checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> None:
    """Call before the first compilation of a program entry point."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
