"""JAX's persistent compilation cache, configured once per entry point.

Every command-line entry point calls :func:`enable_compile_cache` first
thing under its ``__main__`` guard (never at import, so importing a module
changes no process-wide JAX setting).  The cache key includes the cache
path, so the directory must not move between runs:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself and
  this helper sets nothing;
* otherwise the cache lives at ``<checkout>/.jax_cache`` — a fixed path
  inside the checkout (listed in ``.gitignore``), never one derived from a
  temp name, PID or clock.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_ENV", "enable_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/compile_cache.py -> the checkout root
_CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    path = str(_CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
