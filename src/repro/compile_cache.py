"""JAX's persistent compilation cache, placed by the entry points.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the examples) call
:func:`enable_compile_cache` once before their first compile; library
modules never call it.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
keeps its cache in that directory and this sets no other.  Otherwise the
cache lives at one fixed path inside the checkout, ``.jax_cache/``, which
git ignores: a path built from a temporary name, a process id or the clock
would never be found again by the next run.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        CHECKOUT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    # the index's device programs compile in well under the default 1 s
    # floor, so keep every one of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
