"""Persistent compilation cache for the command-line entry points.

Called by each entry point's ``__main__`` (and by ``chip_smoke.py``), never
at library import: a library must not pick a cache directory for its host
program.
"""

from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["enable_compile_cache"]

#: ``<checkout>/.jax_cache`` — fixed, because the directory is part of the
#: cache key: a path that moved between runs would never hit
_DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX; otherwise the
    cache goes to ``.jax_cache/`` at the checkout root (git-ignored).
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(_DEFAULT_DIR))
    return str(_DEFAULT_DIR)
