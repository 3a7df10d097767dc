"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``launch/serve.py``, the serving bench)
call :func:`enable_compile_cache` once at start-up; nothing calls it at
import time.  When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and no other directory is configured.  Otherwise the cache lives at
one fixed path inside the checkout, ``<repo>/.jax_cache`` (git-ignored):
the directory is part of what makes a later run hit the cache, so it never
depends on a temp dir, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
