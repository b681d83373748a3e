"""Where JAX keeps its persistent compilation cache.

Entry points (`chip_smoke.py`, `repro.launch.valuate`,
`repro.launch.valuation_serve`) call
`enable_compile_cache()` once at start-up, never at import:

  * with `JAX_COMPILATION_CACHE_DIR` set, JAX reads that directory itself
    and nothing is set here, so the caller decides where compiles go;
  * otherwise the cache goes to `<checkout>/.jax_cache`, a fixed path
    (the directory is part of the cache key, so a moving one never hits)
    that `.gitignore` excludes.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

# src/repro/launch/compile_cache.py -> the checkout root is three levels up
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory
    (see the module docstring for which one)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
