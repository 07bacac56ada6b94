"""Metrics registry behavior + the ``obs_metrics/v1`` schema pin, and the
tuning-cache hit/miss/stale counters (ISSUE 5 satellite)."""
import contextlib
import json
import os

import pytest

from elemental_tpu.obs import metrics as m


# ---------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------

def test_counters_gauges_histograms():
    reg = m.MetricsRegistry()
    reg.inc("op_calls", op="lu")
    reg.inc("op_calls", op="lu")
    reg.inc("op_calls", op="qr")
    reg.inc("redist_bytes", 100, label="x")
    reg.set_gauge("cache_entries", 3)
    reg.set_gauge("cache_entries", 5)
    reg.observe("phase_seconds", 0.5, driver="lu", phase="panel")
    reg.observe("phase_seconds", 1.5, driver="lu", phase="panel")
    assert reg.counter_value("op_calls", op="lu") == 2
    assert reg.counter_value("op_calls", op="qr") == 1
    assert reg.counter_value("op_calls", op="absent") == 0
    assert reg.counter_value("redist_bytes", label="x") == 100
    doc = reg.to_doc()
    gauges = {g["name"]: g["value"] for g in doc["gauges"]}
    assert gauges == {"cache_entries": 5}           # gauge = last write
    (h,) = doc["histograms"]
    assert h["count"] == 2 and h["sum"] == 2.0
    assert h["min"] == 0.5 and h["max"] == 1.5 and h["mean"] == 1.0
    assert h["labels"] == {"driver": "lu", "phase": "panel"}
    # cumulative buckets end at +Inf with the full count
    assert h["buckets"][-1] == {"le": "+Inf", "count": 2}
    by_le = {b["le"]: b["count"] for b in h["buckets"]}
    assert by_le[1.0] == 1 and by_le[10.0] == 2


def test_schema_pin_round_trip():
    reg = m.MetricsRegistry()
    reg.inc("op_calls", op="gemm")
    reg.observe("phase_seconds", 1e-7, driver="gemm", phase="panel")
    doc = json.loads(reg.to_json(run="r6"))
    assert doc["schema"] == m.SCHEMA == "obs_metrics/v1"
    assert set(doc) == {"schema", "counters", "gauges", "histograms", "run"}
    for row in doc["counters"] + doc["gauges"]:
        assert set(row) == {"name", "labels", "value"}
    for h in doc["histograms"]:
        assert {"name", "labels", "count", "sum", "min", "max", "mean",
                "buckets"} <= set(h)
        for b in h["buckets"]:
            assert set(b) == {"le", "count"}
    # sub-1us observation lands in the first bucket
    assert doc["histograms"][0]["buckets"][0]["count"] == 1


def test_scoped_isolation():
    m.inc("outer_counter", outer=True)
    with m.scoped() as reg:
        m.inc("inner_counter")
        assert m.current() is reg
        assert reg.counter_value("inner_counter") == 1
        assert reg.counter_value("outer_counter", outer=True) == 0
    assert m.current().counter_value("inner_counter") == 0


def test_repeated_weighs_every_tick_in_the_block():
    """A rolled loop's body is traced once and run ``trips`` times."""
    with m.scoped() as reg:
        m.inc("tick")
        with m.repeated(4):
            m.inc("tick")
            m.inc("tick", 2, kind="x")
            with m.repeated(3):
                m.inc("tick")
        m.inc("tick")
        assert reg.counter_value("tick") == 1 + 4 + 12 + 1
        assert reg.counter_value("tick", kind="x") == 8


def test_label_coercion_keeps_json_safe():
    reg = m.MetricsRegistry()
    reg.inc("c", label=(1, 2))              # non-scalar label -> str()
    doc = reg.to_doc()
    json.dumps(doc)
    assert doc["counters"][0]["labels"] == {"label": "(1, 2)"}


# ---------------------------------------------------------------------
# tune-cache events (satellite: visibility for silently rejected files)
# ---------------------------------------------------------------------

@pytest.fixture
def cache_env(tmp_path, monkeypatch):
    from elemental_tpu.tune import cache as tc
    monkeypatch.setenv(tc.ENV_DIR, str(tmp_path))
    from elemental_tpu.tune.policy import clear_memo
    clear_memo()
    yield tmp_path
    clear_memo()


def _key():
    from elemental_tpu.tune import cache as tc
    return tc.make_key("cholesky", (4096, 4096), "float32", (2, 2), "cpu")


def test_cache_load_counts_hit_miss(cache_env):
    from elemental_tpu.tune import cache as tc
    key = _key()
    with m.scoped() as reg:
        assert tc.load(key) is None
        assert reg.counter_value("tune_cache_events", op="cholesky",
                                 event="miss") == 1
        tc.save(key, {"nb": 512})
        assert reg.counter_value("tune_cache_events", op="cholesky",
                                 event="write") == 1
        assert tc.load(key) is not None
        assert reg.counter_value("tune_cache_events", op="cholesky",
                                 event="hit") == 1


def test_cache_load_counts_stale_schema_and_mismatch(cache_env):
    from elemental_tpu.tune import cache as tc
    key = _key()
    path = key.path()
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with m.scoped() as reg:
        with open(path, "w") as f:
            json.dump({"schema": "tuning_cache/v0", "config": {"nb": 1}}, f)
        assert tc.load(key) is None
        assert reg.counter_value("tune_cache_events", op="cholesky",
                                 event="stale_schema") == 1
        with open(path, "w") as f:
            json.dump({"schema": tc.SCHEMA, "op": "lu",
                       "bucket": [4096, 4096], "dtype": "float32",
                       "grid": [2, 2], "backend": "cpu",
                       "config": {"nb": 1}}, f)
        assert tc.load(key) is None
        assert reg.counter_value("tune_cache_events", op="cholesky",
                                 event="key_mismatch") == 1
        with open(path, "w") as f:
            f.write("{torn json")
        assert tc.load(key) is None
        assert reg.counter_value("tune_cache_events", op="cholesky",
                                 event="unparsable") == 1


def test_cache_scan_reports_rejects(cache_env):
    from elemental_tpu.tune import cache as tc
    tc.save(_key(), {"nb": 512})
    with open(os.path.join(cache_env, "lu__stale.json"), "w") as f:
        json.dump({"schema": "tuning_cache/v0"}, f)
    with open(os.path.join(cache_env, "qr__torn.json"), "w") as f:
        f.write("{")
    with m.scoped() as reg:
        docs, rejects = tc.scan()
        assert [d["op"] for d in docs] == ["cholesky"]
        assert {(r["file"], r["reason"]) for r in rejects} == {
            ("lu__stale.json", "stale_schema"), ("qr__torn.json", "unparsable")}
        assert reg.counter_value("tune_cache_events", op="lu",
                                 event="stale_schema") == 1
        assert reg.counter_value("tune_cache_events", op="qr",
                                 event="unparsable") == 1
    # entries() keeps its historical valid-only contract
    assert [d["op"] for d in tc.entries()] == ["cholesky"]


def test_tune_show_surfaces_invalid_files(cache_env, capsys):
    """`python -m perf.tune show` prints INVALID rows for rejected files
    (previously: silent) plus the process event counters."""
    from elemental_tpu.tune import cache as tc
    from perf.tune import cmd_show
    tc.save(_key(), {"nb": 512})
    with open(os.path.join(cache_env, "lu__stale.json"), "w") as f:
        json.dump({"schema": "tuning_cache/v0"}, f)
    with m.scoped():
        assert cmd_show(None) == 0
    out = capsys.readouterr().out
    assert "1 invalid" in out
    assert "INVALID lu__stale.json" in out and "stale_schema" in out
    assert "tune_cache_events (this process):" in out
    # filtered view keeps the reject visible only for its own op
    with m.scoped():
        cmd_show("lu")
    out = capsys.readouterr().out
    assert "INVALID lu__stale.json" in out
    with m.scoped():
        cmd_show("cholesky")
    out = capsys.readouterr().out
    assert "INVALID" not in out


# ---------------------------------------------------------------------
# redist_unpack: the engine's interleave, counted where it is traced
# (ISSUE 29)
# ---------------------------------------------------------------------

@contextlib.contextmanager
def _fresh_engine_jits():
    """The engine's four jitted entries rebuilt around NEW function objects,
    for the time of the block: jax keys its trace cache by the function, so
    whatever is traced under the block runs the entries' Python again (the
    counters tick at trace time), whatever an earlier test left cached, and
    no cache any other test compiled into is cleared."""
    import jax
    from elemental_tpu.redist import engine

    def anew(fn):
        return lambda *args: fn(*args)
    with pytest.MonkeyPatch.context() as patch:
        for name, static in (("_redistribute_jit", (1, 2, 3, 4, 5)),
                             ("_redistribute_direct_jit", (1, 2, 3, 4, 5)),
                             ("_redistribute_q8_jit", (1,)),
                             ("_panel_spread_jit", (1, 2))):
            patch.setattr(engine, name, jax.jit(
                anew(getattr(engine, name).__wrapped__),
                static_argnums=static))
        yield


def _relayout_counts(name, fn):
    """``{impl + dim: ticks}`` of one fresh trace of the engine's entries
    under ``fn``."""
    with _fresh_engine_jits(), m.scoped() as reg:
        fn()
    return {dict(labels)["impl"] + str(dict(labels)["dim"]): n
            for (_, labels), n in reg.counters(name).items()}


def _unpack_counts(shape, dtype="float32"):
    """``redist_unpack`` counters of one fresh trace of [MC,MR] ->
    [STAR,STAR] on 2x2 (two unpacks: columns, then rows)."""
    import jax
    import numpy as np
    import elemental_tpu as el
    grid = el.Grid(jax.devices()[:4], height=2)
    A = el.from_global(np.ones(shape, dtype), el.MC, el.MR, grid=grid)
    return _relayout_counts(
        "redist_unpack", lambda: el.redistribute(A, el.STAR, el.STAR))


@pytest.mark.parametrize("shape,dtype,want", [
    pytest.param((512, 512), "float32", "tiled", id="aligned-f32"),
    pytest.param((512, 19), "float32", "generic", id="ragged"),
    pytest.param((512, 8), "float32", "generic", id="narrow"),
    pytest.param((512, 512), "float64", "generic", id="aligned-f64"),
])
def test_redist_unpack_says_whether_the_blocks_are_whole_tiles(shape, dtype,
                                                               want):
    assert _unpack_counts(shape, dtype) == {want + "1": 1, want + "0": 1}


@pytest.mark.parametrize("op", ["hpd_solve", "lu_solve"])
def test_one_chip_solves_never_reach_the_unpack(op, monkeypatch):
    """The two one-chip benchmark cells are bypassed by construction: on a
    1x1 grid the compiled solve is the same optimized HLO with the engine's
    interleave replaced by something that cannot be traced, and the counter
    reads nothing."""
    from elemental_tpu.redist import engine
    import jax
    from .test_scopes import _compile, stripped
    jax.clear_caches()              # a cached inner jaxpr is not traced again
    with m.scoped() as reg:
        ours = _compile(op, "1x1")
    assert not reg.counters("redist_unpack")

    def unreachable(*a, **k):
        raise AssertionError("a 1x1 program traced the interleave")
    monkeypatch.setattr(engine, "_interleave", unreachable)
    monkeypatch.setattr(engine, "_interleave_2d", unreachable)
    assert stripped(_compile(op, "1x1")) == stripped(ours)


# ---------------------------------------------------------------------
# redist_filter: the engine's de-interleave, the mirror (ISSUE 32)
# ---------------------------------------------------------------------

@pytest.mark.parametrize("n,nb,want", [
    pytest.param(1024, 512, "tiled", id="whole-tiles"),
    pytest.param(96, 48, "generic", id="ragged"),
])
def test_redist_filter_counts_of_an_lu_step(n, nb, want):
    """``lu`` on 2x2 in two panels.  Step 0 filters four times: the
    panel's write-back [STAR,STAR] -> [MC,MR] (rows, then lanes), the U row
    block's [STAR,MR] -> [MC,MR] (rows: the one that sat behind the partial
    gather's lane interleave) and L21's [STAR,STAR] -> [MC,STAR] (rows);
    the last panel only writes itself back (rows, lanes)."""
    import jax
    import numpy as np
    import elemental_tpu as el
    grid = el.Grid(jax.devices()[:4], height=2)
    A = el.from_global(np.random.default_rng(32).normal(
        size=(n, n)).astype(np.float32), el.MC, el.MR, grid=grid)
    got = _relayout_counts("redist_filter",
                           lambda: el.lu(A, nb=nb, crossover=0))
    assert got == {want + "0": 4, want + "1": 2}


def test_redist_filter_is_silent_on_one_chip_and_costs_the_program_nothing(
        monkeypatch):
    """On 1x1 no entry reaches the de-interleave; on 2x2 the compiled LU
    solve is the same optimized HLO with the counter taken out (it ticks
    at trace time and leaves nothing in the program)."""
    from elemental_tpu.redist import engine
    from .test_scopes import _compile, stripped
    assert not _relayout_counts("redist_filter",
                                lambda: _compile("lu_solve", "1x1"))
    texts = []

    def compile_2x2():
        texts.append(stripped(_compile("lu_solve", "2x2")))
    assert _relayout_counts("redist_filter", compile_2x2)
    monkeypatch.setattr(engine, "_count_relayout", lambda *a, **k: None)
    assert not _relayout_counts("redist_filter", compile_2x2)
    assert texts[0] == texts[1]
