"""JAX's persistent compilation cache, placed once for every entry point.

A cold run at published widths compiles a whole scanned model per jit;
the persistent cache lets a later process load those programs instead.
The cache key includes its directory, so the directory must not move
between runs: it is either the one named from outside or a fixed path in
the checkout.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["configure_compile_cache", "CHECKOUT_CACHE_DIR"]

# <checkout>/.jax_cache -- the checkout root is the directory holding src/
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    other directory is set here.  Otherwise the cache lives in
    :data:`CHECKOUT_CACHE_DIR`.  Call before the first compilation.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
