"""Persistent compilation cache for the entry points.

Called at the start of ``launch/serve.py``, ``launch/train.py`` and
``chip_smoke.py`` — never at import, so the test suite stays uncached.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# fixed, never built from a temp name, a pid or a time: a cache written
# to a directory that moves is never found again
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here. Otherwise the cache lives in ``.jax_cache`` at
    the root of the checkout.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
