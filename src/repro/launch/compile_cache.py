"""JAX's persistent compilation cache, kept at one fixed place.

Entry points call :func:`enable_compile_cache` once, before their first
compile. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
and nothing else is set here. Otherwise the cache lives in
``<checkout>/.jax_cache``: a fixed path, since the path is part of what
a later process must find again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get(ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
