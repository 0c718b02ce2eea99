"""Persistent compilation cache for the launchers.

A process that compiles the same programs as an earlier one (the same
bucket shapes, the same checkout) reads them back instead of compiling
again.
"""
from __future__ import annotations

import os
import pathlib

import jax

# the checkout root: src/repro/launch/cache.py -> parents[3]
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    left alone.  Otherwise the cache is ``.jax_cache/`` at the checkout
    root: a fixed path, so every process of this checkout finds what an
    earlier one wrote.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
