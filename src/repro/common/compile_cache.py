"""Persistent XLA compilation cache for the entry points.

A chip call starts with no compiled code, and a cold compile of the larger
programs takes minutes, so every launcher, the benchmark harness and
``chip_smoke.py`` keep JAX's persistent cache in one place:

  * where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here;
  * otherwise the cache lives at the fixed path ``<repo>/.jax_cache``
    (listed in ``.gitignore``). The directory is part of the cache key, so
    a path that moved between runs would never hit.
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
