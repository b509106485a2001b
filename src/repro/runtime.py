"""Process set-up shared by the entry points (``chip_smoke.py``, the serving
launcher, ``benchmarks/run.py``): JAX's persistent compilation cache.

A compiled program is keyed by, among other things, the cache directory, so
the directory is fixed: ``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX
reads it itself and nothing here overrides it), otherwise ``.jax_cache`` at
the root of the checkout (listed in ``.gitignore``).
"""

from __future__ import annotations

import os
from pathlib import Path

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    import jax

    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
