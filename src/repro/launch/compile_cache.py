"""Where JAX's persistent compilation cache lives for the entry points.

Scripts that drive the chip (``chip_smoke.py``, the benchmark ``main``
functions) call :func:`use_compile_cache` once at start-up; the library
and the tests never do, so importing :mod:`repro` changes no JAX state.

The cache key includes the directory, so the path must not move between
runs: ``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it itself),
otherwise ``<checkout>/.jax_cache`` — never a temp, pid or time-based
path.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
