"""Persistent JAX compilation cache, placed from outside the program.

Entry points (``chip_smoke.py``, ``python -m repro.launch.serve``,
``python -m benchmarks.run``) call :func:`enable` once, before their first
compile; importing this module changes nothing.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself, so no
  directory is set in code.
* Otherwise the cache lives at ``<checkout>/.jax_cache`` — a fixed path
  (the path is part of the cache key, so a moving directory never hits),
  git-ignored.

Kernel compiles take about a second each, so the minimum compile time that
earns a cache entry is lowered from JAX's default of one second.
"""

from __future__ import annotations

import os
import pathlib
from typing import Optional

#: repository checkout holding ``src/repro/launch/compile_cache.py``
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]

#: compiles faster than this are not worth a cache entry
MIN_COMPILE_SECS = 0.1


def enable() -> Optional[str]:
    """Turn the persistent compilation cache on; returns the directory set
    in code (None when ``JAX_COMPILATION_CACHE_DIR`` places it)."""
    import jax
    path = None
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      MIN_COMPILE_SECS)
    return path
