"""Where JAX keeps its persistent compilation cache.

Entry points call ``enable_compile_cache()`` at the start of ``main`` (never
at import time). If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
and nothing is set here; otherwise the cache goes to ``<repo>/.jax_cache``,
a fixed path inside the checkout (the path is part of the cache key, so a
directory that moves between runs never hits).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Place the persistent compilation cache; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
