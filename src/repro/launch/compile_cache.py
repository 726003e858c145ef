"""Where JAX keeps its persistent compilation cache.

Entry points that compile (``chip_smoke.py``, ``benchmarks/run.py``,
``launch/train.py``, ``launch/serve.py``) call ``enable_compile_cache``
before their first compile; importing this module sets nothing.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and nothing else
  is configured — whoever set it owns the cache.
* Otherwise the cache lives at ``.jax_cache/`` in the checkout (listed in
  ``.gitignore``).  The path is part of the cache key, so it is fixed: never
  built from a temporary name, a pid or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache"]

_CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(_CHECKOUT_CACHE))
    return str(_CHECKOUT_CACHE)
