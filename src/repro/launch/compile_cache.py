"""JAX's persistent compilation cache, kept at one fixed place.

The entry points (``chip_smoke.py``, ``examples/lbm_cavity_amr.py``,
``benchmarks/run.py``) call :func:`enable_compile_cache` before their first
compile. Importing ``repro`` never does, so library users and the tests keep
JAX's own defaults.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CACHE_ENV", "DEFAULT_CACHE_DIR", "enable_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache (git-ignored): a fixed path, because the cache only
# serves a later process that looks in the same directory
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Path:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    sets nothing; otherwise the cache goes to :data:`DEFAULT_CACHE_DIR`."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return Path(env)
    import jax  # noqa: PLC0415 — keep importing this module free of JAX set-up

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return DEFAULT_CACHE_DIR
