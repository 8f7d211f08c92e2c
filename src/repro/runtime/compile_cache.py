"""JAX's persistent compilation cache, at one fixed place per checkout.

Every entry point (``launch/serve.py``, ``benchmarks/serving.py``,
``chip_smoke.py``) calls :func:`use_compile_cache` before it compiles, so
processes that compile the same programs share them.  The directory is part
of what makes a later run find an entry, so it never comes from a temp name,
a pid or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "use_compile_cache"]

# <checkout>/.jax_cache (git-ignored); this file is src/repro/runtime/...
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here; otherwise the cache goes to
    :data:`CHECKOUT_CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
