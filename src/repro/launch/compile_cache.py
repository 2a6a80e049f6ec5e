"""JAX's persistent compilation cache, kept where the next run finds it.

A compiled program is reused across processes only when it is written
to the same directory every time, so the directory is fixed: the one
``JAX_COMPILATION_CACHE_DIR`` names (JAX reads that variable itself),
else ``.jax_cache`` at the root of the checkout (git-ignored).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Call before the first compile.  Sets nothing when
    ``JAX_COMPILATION_CACHE_DIR`` is set, so the environment decides."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
