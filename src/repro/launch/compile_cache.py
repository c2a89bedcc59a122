"""Where the launchers keep JAX's persistent compilation cache.

Entry points (``chip_smoke.py``, ``repro.launch.serve_ode``,
``benchmarks/run.py``) call :func:`enable_compile_cache` once, before they
compile anything.  Library code (``repro.core``) never sets a cache.

The cache key includes the directory, so the directory must not move between
runs: it is ``JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads it
itself, and nothing here overrides it), and otherwise the fixed path
``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
