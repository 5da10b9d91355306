"""JAX's persistent compile cache, placed from outside the program.

Programs (``chip_smoke.py``, ``benchmarks/run.py``, ``launch/serve.py``) call
:func:`enable_compile_cache` once at start-up; library modules never do, so
importing ``repro`` changes no JAX setting.
"""
from __future__ import annotations

import os

import jax

# <repo>/.jax_cache: fixed, so the cache key (which includes the directory)
# is the same in every run from this checkout; listed in .gitignore
DEFAULT_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is where JAX already keeps the
    cache, and no other directory is set.  Otherwise the cache goes to
    :data:`DEFAULT_CACHE_DIR`.  Every compile is cached, not only slow ones:
    a cold start compiles dozens of small index programs.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
