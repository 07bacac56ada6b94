"""Where JAX's persistent compilation cache lives.

One rule, used by every entry point that turns the cache on
(``chip_smoke.py``, ``bench.py``, ``bench_serve.py``,
``perf/ab_harness.py``, ``tests/conftest.py``): where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here sets another directory; where it is not, the cache is
``<checkout>/.jax_compile_cache``, derived from this file's own
location (the path is part of the cache key, so it must not move).
"""
from __future__ import annotations

import os

import jax

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(_CHECKOUT, ".jax_compile_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
