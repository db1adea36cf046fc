"""Persistent XLA compilation cache shared by the launchers and chip_smoke.py.

Every process compiles each step program it runs, seconds per program; the
cache lets a later process with the same program skip that. The cache key
includes the directory, so the directory must not move between runs.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache"]

# <checkout>/.jax_cache (listed in .gitignore): a fixed path inside the
# checkout, never one built from a temp name, a pid or the time
_CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where JAX_COMPILATION_CACHE_DIR is set, JAX already reads it and no
    other directory is set here. Otherwise the cache lives at
    _CHECKOUT_CACHE_DIR. Call once per process, before the first compile.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(_CHECKOUT_CACHE_DIR))
    return str(_CHECKOUT_CACHE_DIR)
