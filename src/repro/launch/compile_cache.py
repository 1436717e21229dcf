"""JAX's persistent compilation cache at one fixed place per checkout.

The cache key includes the directory, so a cache that moves never hits.
Called by the entry scripts (``chip_smoke.py``, ``benchmarks/run.py``);
importing the library never touches it.
"""
from __future__ import annotations

import os
import pathlib

CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is changed here; otherwise the cache sits at ``.jax_cache`` in
    the checkout (gitignored).
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
