"""Placement of JAX's persistent compilation cache.

Every entry point that launches device work calls
``enable_compile_cache()`` once, before its first jit (never at
``import ceph_tpu``: imports stay side-effect free).  The path is part
of the cache key, so a directory that moves between runs never hits:
it is one fixed directory inside the checkout, found from ``__file__``
(a deployed copy is not a git repository).  An operator who sets
``JAX_COMPILATION_CACHE_DIR`` owns the placement: JAX reads that
variable itself and this module sets nothing.
"""

from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point the persistent compile cache at its directory (idempotent)
    and return the directory in effect."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
