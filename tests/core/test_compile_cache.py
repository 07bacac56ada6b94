"""Where the persistent compile cache goes (core/compile_cache.py)."""
import os

import jax

from elemental_tpu.core import compile_cache as cc

#: the names an op carries are part of the key; the caller's stack is not
KEYED_BY = {"jax_compilation_cache_include_metadata_in_key": True,
            "jax_traceback_in_locations_limit": 1}


def test_unset_env_gives_checkout_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    seen = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: seen.__setitem__(k, v))
    path = cc.enable_compile_cache()
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(cc.__file__))))
    assert path == os.path.join(root, ".jax_compile_cache")
    assert os.path.exists(os.path.join(root, "chip_smoke.py"))
    assert seen == {"jax_compilation_cache_dir": path, **KEYED_BY}


def test_set_env_wins_and_nothing_is_updated(monkeypatch, tmp_path):
    """No directory is set in code; what an entry is keyed by is."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    seen = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: seen.__setitem__(k, v))
    assert cc.enable_compile_cache() == str(tmp_path)
    assert seen == KEYED_BY


def test_no_code_sets_the_cache_dir_but_the_helper():
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(cc.__file__))))
    hits = []
    for base, dirs, files in os.walk(root):
        dirs[:] = [d for d in dirs if not d.startswith(".")]
        for f in files:
            if f.endswith(".py"):
                p = os.path.join(base, f)
                with open(p) as fh:
                    src = fh.read()
                if '"jax_compilation_cache_dir"' in src \
                        and os.path.abspath(p) != os.path.abspath(
                            __file__):
                    hits.append(os.path.relpath(p, root))
    assert hits == [os.path.join("elemental_tpu", "core",
                                 "compile_cache.py")]
