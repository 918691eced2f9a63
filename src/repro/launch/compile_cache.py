"""JAX's persistent compilation cache for the processes that hold a chip.

A cold process on the TPU compiles every program it runs; the cache lets the
next process of the same checkout load them instead. ``JAX_COMPILATION_CACHE_DIR``
wins when it is set (JAX reads it itself). Otherwise the cache lives at a
fixed path inside the checkout, ``<repo>/.jax_cache``: the path is part of
the cache key, so it must not move between runs.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/compile_cache.py -> the checkout root, three levels up.
DEFAULT_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str | None:
    """Point JAX's persistent cache at its directory; returns that directory.

    On the CPU backend (tests, rehearsals) nothing is turned on and ``None``
    is returned, unless ``JAX_COMPILATION_CACHE_DIR`` asks for it."""
    path = os.environ.get(CACHE_ENV)
    if path:
        return path
    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
