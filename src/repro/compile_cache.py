"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``benchmarks/``, ``examples/``) call
:func:`enable` once, before their first compile.  The directory is placed
from outside the code: where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
reads it itself and nothing is set here.  Otherwise the cache goes to the
fixed ``<checkout>/.jax_cache`` (gitignored), never a temp, pid- or
time-derived path, so a second process finds what the first one wrote.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[2]


def enable() -> str:
    """Turn the persistent cache on; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
