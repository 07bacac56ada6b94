"""The five readers that split ``setup_s`` (ISSUE 39), on a made-up
compile log: the sums, the rest clipped at 0, nothing on an empty log,
nothing where the run states no ``setup_s`` or the program has no log,
and the parts of a real session's set-up making its whole."""
import json
import time

import jax
import pytest

import bench_copy
import run as harness
import setup_parts
from elemental_tpu.obs.compile_log import STAGES, CompileLog

EVENT = {stage: event for event, stage in STAGES.items()}
NAMES = ("setup_trace_s", "setup_lower_s", "setup_backend_s",
         "setup_cache_misses", "setup_rest_s")


def readers():
    return {name: harness.load_module(bench_copy.BENCH, "layer_metrics", name)
            for name in NAMES}


def span(log, stage, name, start, end, inside=(), cache=None):
    """One made-up span, the spans ``inside`` it fed first."""
    log.on_start(EVENT[stage], start, fun_name=name)
    for child in inside:
        span(log, *child)
    if cache is not None:
        log.on_event("/jax/compilation_cache/compile_requests_use_cache")
        if cache == "hit":
            log.on_event("/jax/compilation_cache/cache_hits")
    log.on_span(EVENT[stage], start, end, fun_name=name)


def made_up_log():
    """A set-up of three programs: 10 s of trace that hold 3 s of inner
    traces, 4 s of lowering that hold 1 s of trace, a compile of 20 s that
    missed the cache and two loads of 2 s that hit it."""
    log = CompileLog()
    span(log, "trace", "bench_solve", 0.0, 10.0,
         inside=[("trace", "add", 1.0, 2.0), ("trace", "less", 4.0, 6.0)])
    span(log, "lower", "jit(bench_solve)", 10.0, 14.0,
         inside=[("trace", "_where", 11.0, 12.0)])
    span(log, "backend", "jit(bench_solve)", 14.0, 34.0, cache="miss")
    span(log, "backend", "jit(generate)", 35.0, 37.0, cache="hit")
    span(log, "backend", "jit(check)", 38.0, 40.0, cache="hit")
    return log


WANT = {"setup_trace_s": 11.0, "setup_lower_s": 3.0,
        "setup_backend_s": 24.0, "setup_cache_misses": 1,
        "setup_rest_s": 12.0}


@pytest.mark.parametrize("name", NAMES)
def test_reader_on_a_made_up_log(name, monkeypatch):
    monkeypatch.setattr(setup_parts, "log", made_up_log)
    reader = readers()[name]
    assert reader.MOVES == "setup_s"
    assert reader.read(None, {"setup_s": 50.0}) == pytest.approx(WANT[name])


def test_the_parts_make_the_whole_and_the_rest_is_never_negative(
        monkeypatch):
    monkeypatch.setattr(setup_parts, "log", made_up_log)
    got = {name: r.read(None, {"setup_s": 50.0})
           for name, r in readers().items()}
    assert sum(v for name, v in got.items() if name.endswith("_s")) \
        == pytest.approx(50.0)
    # a clock that read less than the spans it holds: clipped, not negative
    assert readers()["setup_rest_s"].read(None, {"setup_s": 30.0}) == 0.0
    json.dumps(got)


@pytest.mark.parametrize("case", ["empty-log", "no-log", "no-setup_s"])
@pytest.mark.parametrize("name", NAMES)
def test_reader_finds_nothing_to_read(name, case, monkeypatch):
    run = {"setup_s": 50.0}
    if case == "empty-log":
        monkeypatch.setattr(setup_parts, "log", CompileLog)
    elif case == "no-log":          # a parent commit: no such module
        monkeypatch.setattr(setup_parts, "log", lambda: None)
    else:                           # the harness's own hand-made runs
        monkeypatch.setattr(setup_parts, "log", made_up_log)
        run = {"facts": {"chips": 1}}
    assert readers()[name].read(None, run) is None


def test_the_log_is_the_program_s_own():
    from elemental_tpu.obs import compile_log
    assert setup_parts.log() is compile_log.LOG


def test_a_session_s_set_up_is_split_into_its_parts(tmp_path):
    """A real session on the CPU, as ``run.py`` builds it: the log holds
    the three programs, the parts are non-negative and sum to the clock
    around them.  Counts of a rehearsal, not a measurement."""
    from elemental_tpu.obs import compile_log
    bench_dir = bench_copy.make(tmp_path / "benchmark")
    harness.enable_cache()
    _cell, config, traffic = harness.resolve(bench_dir, "t.lu.1x1")
    kind = harness.load_module(bench_dir, "kinds", config["kind"])
    before, t0 = compile_log.LOG.totals(), time.perf_counter()
    kind.setup(config, traffic, jax.devices()[:1], 2147483999)
    wall = time.perf_counter() - t0
    after = compile_log.LOG.totals()
    spent = {stage: after["seconds"][stage] - before["seconds"][stage]
             for stage in after["seconds"]}
    assert all(s > 0.0 for s in spent.values()), spent
    assert sum(spent.values()) <= wall
    assert after["requests"] - before["requests"] >= 3
    names = {r.fun_name for r in compile_log.LOG.records}
    assert {"bench_solve", "jit(bench_solve)", "jit(generate)",
            "jit(check)"} <= names
    got = {name: r.read(None, {"setup_s": 1e9})
           for name, r in readers().items()}
    assert all(v is not None and v >= 0 for v in got.values())
