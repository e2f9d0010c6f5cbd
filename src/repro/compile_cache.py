"""JAX's persistent compilation cache, kept at one fixed place.

A cold run of a full-width replay spends minutes compiling; the cache
lets the next process that compiles the same program read it back, so
the directory must stay the same from run to run: it is
``JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads the variable
itself, and nothing here overrides it), else ``.jax_cache`` at the root
of this checkout.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
