"""Where JAX's persistent compilation cache lives.

Entry points (``chip_smoke.py``, ``examples/serve_shared.py``,
``benchmarks/run.py``) call :func:`enable_compile_cache` from their
``main``; importing this module changes nothing.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and nothing is
  set in code.
* otherwise: ``<checkout>/.jax_cache`` (listed in ``.gitignore``).  The
  path is fixed — it is part of the cache key, so a per-run temp name
  would never hit.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: the repository root: src/repro/launch/compile_cache.py -> parents[3]
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
