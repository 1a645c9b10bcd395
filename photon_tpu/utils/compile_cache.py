"""Where the persistent XLA compile cache lives.

The cache key includes the directory, so the path must never move between
starts: either the operator places it (``JAX_COMPILATION_CACHE_DIR``, which
JAX reads by itself) or it is the fixed ``.jax_cache`` beside the package.
"""

from __future__ import annotations

import os
import pathlib

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compile cache at a stable directory and return
    it. Call from an entry point's ``main()``, never at import."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
