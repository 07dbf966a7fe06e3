"""JAX's persistent compilation cache at a fixed place.

Entry points (``chip_smoke.py``, ``examples/serve_index.py``, the benchmark
plumbing) call :func:`enable_compile_cache` before their first compile.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses that directory
and nothing else is set; otherwise the cache goes to ``<checkout>/.jax_cache``
(ignored by git).  The path is part of every cache key, so it is fixed: never
derived from a temporary name, a pid or the time.
"""
from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = pathlib.Path(__file__).resolve().parents[2]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on for every compile of this process and
    return its directory."""
    import jax

    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # keep every executable: the serving path is many sub-second compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
