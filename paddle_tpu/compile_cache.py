"""The one place that turns on JAX's persistent compilation cache.

Every entry point that compiles something worth keeping calls
``enable_compile_cache()`` before its first jit: ``chip_smoke.py``,
``bench.py``, the scripts under ``tools/``, the fleet's worker
processes and ``tests/conftest.py``.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and this
module sets no directory. Where it is not, the cache lives in ONE fixed
directory inside the checkout (git-ignored): the directory is part of
the cache key, so a home, temporary, pid- or time-derived path never
hits from the next process.
"""
from __future__ import annotations

import os
from typing import Optional

__all__ = ["CACHE_DIR", "enable_compile_cache"]

#: <checkout>/.jax_cache — listed in .gitignore
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def enable_compile_cache() -> Optional[str]:
    """Returns the directory set in code, or None when the environment
    names one (JAX then uses that and nothing is set here)."""
    import jax

    # the default threshold (1 s) skips most of a test suite's programs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
