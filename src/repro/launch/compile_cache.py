"""JAX's persistent compilation cache for the entry-point scripts.

`enable_compile_cache()` is called by `chip_smoke.py` and
`examples/train_e2e.py` before their first compile; importing the library
never turns the cache on.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# a fixed directory at the root of the checkout (listed in .gitignore): a
# cache whose path changes from run to run is never found again
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it, and no
    other directory is set; otherwise the cache lives in `CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
