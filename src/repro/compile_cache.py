"""JAX's persistent compilation cache for the repo's entry points.

Library code never touches the cache; ``chip_smoke.py``, the benchmarks
and the examples call :func:`enable_compile_cache` once at start-up.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the cache's home when the environment names none: inside the checkout
#: (gitignored) and fixed, so every process run from this checkout finds
#: the entries an earlier one wrote
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here. Otherwise the cache goes to :data:`DEFAULT_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
