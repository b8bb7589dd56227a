"""JAX persistent compilation cache for this checkout's entry points.

Scripts (``chip_smoke.py``, ``benchmarks/run.py``, ``benchmarks/serve_load.py``)
call :func:`enable_compile_cache` once at start-up; importing the library
never touches the cache. When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads
it itself and nothing is set here. Otherwise the cache lives at one fixed
directory inside the checkout (``<repo>/.jax_cache``, git-ignored), so a
later run finds what an earlier one compiled; a path built from a temp name,
a pid or the time would change from run to run and never be found again.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_DIR", "enable_compile_cache"]

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
