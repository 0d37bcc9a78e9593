"""JAX's persistent compilation cache, placed from outside or at a fixed path.

The search compiles one program per pool bucket, GP bucket and stack width,
most of them in well under a second, and a cold process compiles them all
again.  `enable_compile_cache()` turns the persistent cache on for every such
program.  Entry points (`bench/run.py`, `chip_smoke.py`, `benchmarks/run.py`,
the examples) call it once, before their first compile; library code never
does.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache: fixed, so one checkout's runs find each other's
# entries (the path is part of the cache key); listed in .gitignore.
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Cache every compiled program; returns the cache directory.

    `JAX_COMPILATION_CACHE_DIR`, when set, is the directory (jax reads it
    itself; nothing here overrides it).  Otherwise the cache lives at
    `DEFAULT_DIR`."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
