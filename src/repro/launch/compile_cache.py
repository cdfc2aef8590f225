"""JAX persistent compilation cache for the repo's entry points.

The engine compiles one program per plan shape and capacity, so a cold
process pays for every compile again.  Entry points (``chip_smoke.py``,
``benchmarks/run.py``, the examples' ``main``) call
:func:`enable_compile_cache` before their first ``jit``; importing this
module changes nothing.
"""
from __future__ import annotations

import os

import jax

# <repo>/.jax_cache: a fixed path inside the checkout (listed in
# .gitignore), so every run of one checkout finds the same entries
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
    ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps the
    cache there and no other directory is set.  Otherwise the cache goes
    to ``<repo>/.jax_cache``.  Every program is written, however fast it
    compiled: the engine's per-shape programs mostly compile in under
    JAX's default one-second threshold and would never be cached.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.normpath(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
