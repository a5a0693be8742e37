"""The repo's one shim over JAX process settings.

* :func:`x64` scopes float64 to one block. The decision path
  (``repro.fleet``) runs its closed forms, tail inversions and simulators in
  float64 inside it; the float32/bf16 model stack never sees x64 switched on
  globally.
* :func:`enable_compilation_cache` turns on JAX's persistent compilation
  cache. Entry points (``chip_smoke.py``, the launchers, ``benchmarks.run``)
  call it from ``main()``; nothing calls it at import time or from tests.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT", "DEFAULT_CACHE_DIR", "enable_compilation_cache", "x64"]

CHECKOUT = Path(__file__).resolve().parents[2]
# a fixed path: the cache key includes it, so a directory that moves never hits
DEFAULT_CACHE_DIR = CHECKOUT / ".jax_cache"


def x64():
    """Context manager: float64 semantics for the enclosed block only."""
    return jax.enable_x64(True)


def enable_compilation_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as JAX reads it and no
    other directory is set here; otherwise the cache goes to
    ``<checkout>/.jax_cache``.
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    jax.config.update("jax_enable_compilation_cache", True)
    return jax.config.jax_compilation_cache_dir
