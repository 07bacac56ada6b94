"""Where JAX's persistent compilation cache lives.

One rule, used by every entry point that turns the cache on
(``chip_smoke.py``, ``benchmark/run.py``, ``tests/conftest.py``): where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here sets another directory; where it is not, the cache is
``<checkout>/.jax_compile_cache``, derived from this file's own
location (the path is part of the cache key, so it must not move).

What an entry is keyed by.  The drivers name their phases inside the
compiled program (``jax.named_scope``, see :mod:`elemental_tpu.obs`), and
a device trace is split by those names.  JAX's default key leaves an
op's metadata out, so a cache that another build of the library filled
would hand this build an executable that carries the OTHER build's
names -- or none.  So the names go into the key
(``jax_compilation_cache_include_metadata_in_key``), and an op's location
is its own frame, not the Python call stack above it
(``jax_traceback_in_locations_limit`` 1): the key then depends on the
library and its names, not on which line of which caller traced the
driver (a second trace of one program from another line of one script
must hit the entry the first one wrote).
"""
from __future__ import annotations

import os

import jax

from ..obs import compile_log

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.
    The compile log (:mod:`elemental_tpu.obs.compile_log`) starts here
    too: what is traced, lowered, compiled or read back from this cache
    is counted and timed from now on."""
    compile_log.install()
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_traceback_in_locations_limit", 1)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(_CHECKOUT, ".jax_compile_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
