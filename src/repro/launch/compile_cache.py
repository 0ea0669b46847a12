"""Persistent compilation cache for the entry points.

A full-width compile of the serving step takes tens of seconds; JAX's
persistent cache lets later processes on the same machine reuse it. The
cache key includes the directory, so the directory is fixed.
"""
from __future__ import annotations

import os

import jax

# one fixed directory inside the checkout (listed in .gitignore)
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable() -> str:
    """Turn the persistent compilation cache on and return its directory.

    ``$JAX_COMPILATION_CACHE_DIR``, when set, is used as it is (JAX reads
    it itself) and nothing else is set; otherwise the cache goes to
    ``CACHE_DIR``. Entry points call this; importing sets nothing."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
