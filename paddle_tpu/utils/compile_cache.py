"""Where JAX's persistent compilation cache lives — one rule for
bench.py, chip_smoke.py and the tests.

``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and nothing here
names a directory, so whoever runs the program places the cache. Unset:
one fixed directory inside the checkout (git-ignored). The path is part
of the cache key, so it never carries a pid, a time or a temp dir.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE_DIR = str(
    pathlib.Path(__file__).resolve().parents[2] / ".jax_cache")


def enable_compile_cache(min_compile_secs: float = 1.0) -> str:
    """Turn the persistent cache on; returns the directory in use."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(min_compile_secs))
    return jax.config.jax_compilation_cache_dir
