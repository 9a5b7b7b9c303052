"""JAX's persistent compilation cache at one fixed place.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
is set here. Otherwise the cache lives in ``.jax_cache`` at the root of the
checkout: a fixed path, since the path is part of what the cache matches,
and inside the checkout, which is all the program writes to.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent cache; returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
