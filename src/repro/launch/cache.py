"""Persistent compilation cache placement, shared by every entry point.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets no other directory.  Otherwise the cache lives at one fixed path
inside the checkout (``<repo>/.jax_cache``, git-ignored): JAX keys cache
entries on the directory, so a temporary or per-process path would never
hit.  Call :func:`enable_compile_cache` before the first compile.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: ``src/repro/launch/cache.py`` -> the checkout root
REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
