"""JAX's persistent compilation cache at one fixed place.

Entry points (`chip_smoke.py`, `benchmarks/run.py`, the examples) call
`use_compile_cache` before their first compile, so a second process that
compiles the same programs reads them back instead of compiling again.
"""
from __future__ import annotations

import os
import pathlib

import jax

# Fixed path inside the checkout: the cache directory is part of what a
# later run must match, so it never carries a temp name, a pid or a time.
CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> None:
    """Turn the persistent cache on.  Where ``JAX_COMPILATION_CACHE_DIR`` is
    set, JAX already keeps its cache there and nothing is set in code;
    otherwise the cache lives at `CACHE_DIR`."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
