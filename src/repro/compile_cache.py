"""JAX's persistent compilation cache, placed from outside or at a fixed path.

Every entry point that compiles the search (``chip_smoke.py``,
``repro.launch.serve``, the cluster shard server, ``benchmarks.run``) calls
``enable_compile_cache()`` once at start-up, so a second process with the
same programs loads them instead of compiling again.
"""

from __future__ import annotations

import os

import jax

__all__ = ["enable_compile_cache", "DEFAULT_CACHE_DIR"]

# <checkout>/.jax_cache (git-ignored).  A fixed path, so the next process
# started from the same checkout finds what this one wrote.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is honoured as JAX reads it;
    otherwise the cache goes to ``DEFAULT_CACHE_DIR``.  The minimum compile
    time and entry size drop to zero, so kernel-sized programs are cached
    too, not only the ones that took over a second."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
