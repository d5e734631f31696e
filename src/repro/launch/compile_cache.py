"""Where JAX keeps its persistent compilation cache.

Entry points that run on the chip (``chip_smoke.py``, ``repro.launch.serve``)
call :func:`enable_compile_cache` once, before compiling anything; library
code and tests never do.  The cache key includes the directory, so the
directory must not move between runs: ``JAX_COMPILATION_CACHE_DIR``, when
set, wins (JAX reads it itself and nothing else is set here); otherwise the
cache lives at a fixed path inside the checkout.
"""

from __future__ import annotations

import os
import pathlib

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_CACHE_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    import jax

    # every compile is worth keeping: the fused kernel compiles in about
    # a second, under JAX's default one-second threshold
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
