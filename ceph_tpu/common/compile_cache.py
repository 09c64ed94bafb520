"""Where JAX keeps its persistent compile cache for programs that run
on the chip (chip_smoke.py, bench.py, scripts/placement_bench.py,
scripts/perf_probe.py).

A cache directory is part of the cache key, so it must not move from
run to run: never a temp, pid or time-based path."""
from __future__ import annotations

import os

#: used when JAX_COMPILATION_CACHE_DIR is unset; listed in .gitignore
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def use_compile_cache() -> str:
    """Turn on the persistent compile cache and return its directory.

    Where JAX_COMPILATION_CACHE_DIR is set, JAX reads it itself and
    this sets nothing.  Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
