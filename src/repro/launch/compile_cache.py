"""JAX's persistent compilation cache, turned on the same way by every
entry point (``chip_smoke.py``, ``launch/train_cnn.py``,
``bench/run.py``, ``scripts/tune.py``) before its first compile.

The directory is ``$JAX_COMPILATION_CACHE_DIR`` when that is set, and
otherwise the fixed ``<checkout>/.cache/jax`` (listed in ``.gitignore``):
the path is part of what a later process must find again, so it never
depends on the working directory.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".cache" / "jax"
# JAX caches only compiles slower than its 1 s default; a conv kernel
# compiles in 0.1-2 s, so most of the kernels would never be cached.
MIN_COMPILE_TIME_S = 0.05


def compile_cache_dir() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.cache/jax``."""
    return os.environ.get(ENV_VAR) or str(DEFAULT_DIR)


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at ``compile_cache_dir()`` and lower its
    minimum compile time; returns the directory.  Call before the first
    compile of the process."""
    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      MIN_COMPILE_TIME_S)
    return path
