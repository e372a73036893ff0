"""JAX's persistent compilation cache, placed from outside the library.

Entry points (the benchmark harness) call
:func:`enable_compile_cache` once before their first compile; library code
and tests never do. The cache key includes the directory, so the default is
a fixed path inside the checkout rather than anything temporary.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory:
    ``$JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it itself, so nothing
    else is configured), else ``<repo>/.jax_cache``."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
