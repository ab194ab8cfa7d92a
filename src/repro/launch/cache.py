"""JAX's persistent compilation cache, set up in one place.

Every entry point (``chip_smoke.py``, ``benchmarks/run.py``, the
examples) calls ``enable_compile_cache()`` before its first compile.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads that directory
itself and this sets no other.  Otherwise the cache lives at
``<checkout>/.jax_cache``: a fixed path, because the path is part of
what a later run must match to find its entries again.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
