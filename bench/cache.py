"""JAX's persistent compilation cache at one fixed path inside the checkout.

Imported by the entry points before anything imports JAX: JAX reads these
settings when it is imported, and settles its cache on the first compile.
"""
import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[1] / ".jax_cache"


def use_compile_cache() -> None:
    """Cache every program, however short its compile, under ``CACHE_DIR``."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
