"""Persistent XLA compilation cache for the entry points.

A process that compiles a program JAX has compiled before, with the same
shapes, flags and toolchain, reads the executable back from this cache
instead of compiling it again. The cache's path is part of its key, so it
lives at one fixed place per checkout. Entry points call
`enable_compile_cache` after parsing their arguments; importing this
module changes nothing.
"""

from __future__ import annotations

import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the cache on and return its directory. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    other path is set here; otherwise the cache is ``<checkout>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
