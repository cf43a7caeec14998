"""JAX's persistent compilation cache, at one fixed place per checkout.

JAX keys cache entries by program and compiler, not by where the cache
lives, but a cache in a directory named after a temporary name, a PID or
the time is never found again.  :func:`use_compile_cache` is called once at
the start of every entry point that compiles for the device.
"""

from __future__ import annotations

import os
import sys
from pathlib import Path

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/launch/``).
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    A ``JAX_COMPILATION_CACHE_DIR`` already in the environment is left as
    it is (JAX reads it itself).  Otherwise the cache goes to
    :data:`DEFAULT_DIR`.  The variable is also exported, so worker
    processes and a ``jax`` imported later pick up the same directory.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = str(DEFAULT_DIR)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax = sys.modules.get("jax")
    if jax is not None:
        jax.config.update("jax_compilation_cache_dir", path)
    return path
