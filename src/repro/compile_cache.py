"""JAX's persistent compilation cache, placeable from outside.

Every entry point calls ``enable()`` before its first compile.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing else
is set.  Otherwise the cache lives at a fixed path inside the checkout,
``<repo>/.jax_cache/`` (listed in ``.gitignore``): a path derived from a
temporary name, a pid or the time would never hit again.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["DEFAULT_DIR", "enable"]

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
