"""JAX's persistent compilation cache at a fixed place.

Every process of a benchmark or smoke run would otherwise compile its
engines and models from cold.  The cache key includes the directory, so the
directory must not move between runs.
"""

from __future__ import annotations

import os

# <checkout>/.jax_cache (this file is <checkout>/src/repro/launch/...)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")

# Below JAX's 1 s default, so that the engines' CPU compiles are kept too.
MIN_COMPILE_SECS = 0.5


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, where set, is left as JAX read it;
    otherwise the cache lives in ``DEFAULT_DIR``.  Call at program start,
    before the first compile."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      MIN_COMPILE_SECS)
    return path
