"""Where JAX's persistent compilation cache lives.

The cache key includes the directory, so the directory must not move
between runs: ``$JAX_COMPILATION_CACHE_DIR`` when the environment sets it
(JAX reads that variable itself), else the fixed ``<repo>/.jax_cache``.
"""
from __future__ import annotations

import os
import pathlib

DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.
    Call once, before the first compile."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
