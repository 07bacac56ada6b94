"""The compile log (ISSUE 39): ``jax.monitoring``'s trace / lower /
backend spans as records with SELF time, the persistent cache's counters,
``compile/<stage>`` spans of an active ``Tracer`` on the epoch clock, and
the host spans a profiler sees.

Nothing here clears a jit cache (``tests/conftest.py``, and the verify
notes on ``jax.clear_caches()`` under the six-worker run): a compile is
made fresh by wrapping a NEW function object, and a program is made new
to the persistent cache by a constant no earlier run can have used."""
import importlib.util
import os
import shutil
import tempfile
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import monitoring

import elemental_tpu as el
from elemental_tpu import obs
from elemental_tpu.obs import compile_log
from elemental_tpu.obs.compile_log import STAGES, CompileLog

EVENT = {stage: event for event, stage in STAGES.items()}
WAIT = 30.0


@pytest.fixture
def log():
    """A log of this test's own, fed by JAX beside the process's."""
    own = CompileLog()
    monitoring.register_scalar_listener(own.on_start)
    monitoring.register_event_time_span_listener(own.on_span)
    monitoring.register_event_listener(own.on_event)
    monitoring.register_event_duration_secs_listener(own.on_duration)
    try:
        yield own
    finally:
        monitoring.unregister_scalar_listener(own.on_start)
        monitoring.unregister_event_time_span_listener(own.on_span)
        monitoring.unregister_event_listener(own.on_event)
        monitoring.unregister_event_duration_listener(own.on_duration)


def _unique():
    """A constant no program in the persistent cache can hold yet."""
    return float(time.time_ns() % (1 << 40)) + float(os.getpid())


def _fresh(c):
    """A new jitted function (a new object: jax traces it anew) that no
    other test compiles; the same ``c`` gives the same program."""
    def obs39_fresh(x):
        return jnp.sin(x) * c + 1.0
    return jax.jit(obs39_fresh)


# ------------------------------------------------------- what JAX reports

@pytest.mark.parametrize("stage,fun_name", [
    ("trace", "obs39_fresh"), ("lower", "jit(obs39_fresh)"),
    ("backend", "jit(obs39_fresh)")])
def test_a_fresh_jit_gives_one_record_a_stage(log, stage, fun_name):
    before = time.time()
    jax.block_until_ready(_fresh(_unique())(np.ones(8, np.float32)))
    after = time.time()
    found = [r for r in log.records
             if r.stage == stage and "obs39_fresh" in r.fun_name]
    assert [r.fun_name for r in found] == [fun_name]
    (rec,) = found
    assert before <= rec.start <= rec.end <= after
    assert rec.thread == threading.get_ident()
    assert 0.0 <= rec.self_s <= rec.seconds
    assert log.totals()["records"][stage] >= 1


def test_self_times_of_nested_jits_sum_to_the_wall_time_and_not_more(log):
    c = _unique()
    inner = _fresh(c)

    def obs39_outer(x):
        return inner(x).sum() + inner(x * 2.0).max()
    x = np.ones(16, np.float32)
    t0 = time.time()
    jax.jit(obs39_outer).lower(x).compile()
    wall = time.time() - t0
    totals = log.totals()
    outermost = sum(r.seconds for r in log.records)
    assert sum(totals["seconds"].values()) == pytest.approx(outermost,
                                                            rel=1e-9)
    assert outermost <= wall
    # the inner jit's trace lies inside the outer one's, which stands for
    # it in the ring; a plain sum of durations would count it twice
    outer = [r for r in log.records if r.fun_name == "obs39_outer"]
    assert len(outer) == 1 and outer[0].covered >= 1
    assert outer[0].self_s < outer[0].seconds
    assert not any(r.fun_name == "obs39_fresh" for r in log.records)
    assert sum(totals["records"].values()) == sum(
        1 + r.covered for r in log.records)


# ---------------------------------------------------- the nesting rule

def _feed(log, spans):
    """Made-up ``(stage, fun_name, start, end)`` spans of one thread, as
    JAX would report them: every start and end in time order, a span's end
    after the ends of the spans inside it."""
    points = []
    for i, (stage, name, start, end) in enumerate(spans):
        points.append((start, 1, -end, i, "start"))
        points.append((end, 0, -start, i, "end"))
    for _t, _k, _tie, i, what in sorted(points):
        stage, name, start, end = spans[i]
        if what == "start":
            log.on_start(EVENT[stage], start, fun_name=name)
        else:
            log.on_span(EVENT[stage], start, end, fun_name=name)


NESTING = {
    # a child closes before its parent, which takes its place in the ring
    "child-before-parent": (
        [("trace", "outer", 0.0, 10.0), ("trace", "add", 2.0, 3.0),
         ("trace", "less", 5.0, 5.5)],
        {"trace": 10.0, "lower": 0.0, "backend": 0.0},
        [("outer", 8.5, 2)]),
    # tracing goes on inside a lowering: its seconds are trace seconds
    "trace-inside-lower": (
        [("lower", "jit_f", 0.0, 4.0), ("trace", "g", 1.0, 2.5)],
        {"trace": 1.5, "lower": 2.5, "backend": 0.0},
        [("jit_f", 2.5, 1)]),
    # three levels: only the DIRECT children are taken off a span
    "grandchild": (
        [("trace", "a", 0.0, 8.0), ("trace", "b", 1.0, 7.0),
         ("trace", "c", 2.0, 4.0)],
        {"trace": 8.0, "lower": 0.0, "backend": 0.0},
        [("a", 2.0, 2)]),
    # one program after another: nobody covers anybody
    "siblings": (
        [("trace", "f", 0.0, 1.0), ("lower", "jit_f", 1.0, 3.0),
         ("backend", "jit_f", 3.0, 7.0)],
        {"trace": 1.0, "lower": 2.0, "backend": 4.0},
        [("f", 1.0, 0), ("jit_f", 2.0, 0), ("jit_f", 4.0, 0)]),
}


@pytest.mark.parametrize("case", sorted(NESTING))
def test_every_instant_belongs_to_the_innermost_open_span(case):
    spans, seconds, ring = NESTING[case]
    log = CompileLog()
    _feed(log, spans)
    totals = log.totals()
    assert totals["seconds"] == pytest.approx(seconds)
    assert sum(totals["records"].values()) == len(spans)
    assert [(r.fun_name, pytest.approx(r.self_s), r.covered)
            for r in log.records] == ring
    # the parts make the whole: self seconds sum to the outermost spans'
    assert sum(totals["seconds"].values()) == pytest.approx(
        sum(r.seconds for r in log.records))
    assert not log._open


def _in_turn(steps):
    """Run ``[(thread index, callable)]`` in the order given, each on its
    own thread: what two compiling threads interleave."""
    turn = [threading.Event() for _ in steps]
    done = [threading.Event() for _ in steps]
    errors = []

    def worker(mine):
        for i in mine:
            assert turn[i].wait(WAIT)
            try:
                steps[i][1]()
            except Exception as e:          # shown by the assert below
                errors.append(e)
            done[i].set()
    threads = [threading.Thread(target=worker, args=(
        [i for i, s in enumerate(steps) if s[0] == t],))
        for t in sorted({s[0] for s in steps})]
    for th in threads:
        th.start()
    for i in range(len(steps)):
        turn[i].set()
        assert done[i].wait(WAIT)
    for th in threads:
        th.join(WAIT)
        assert not th.is_alive()
    assert not errors


def test_two_threads_nest_each_on_its_own():
    log = CompileLog()
    tr, lo = EVENT["trace"], EVENT["lower"]
    _in_turn([
        (0, lambda: log.on_start(tr, 0.0, fun_name="a")),
        (1, lambda: log.on_start(lo, 1.0, fun_name="jit_b")),
        (0, lambda: log.on_start(tr, 2.0, fun_name="a_child")),
        (1, lambda: log.on_span(lo, 1.0, 3.0, fun_name="jit_b")),
        (0, lambda: log.on_span(tr, 2.0, 4.0, fun_name="a_child")),
        (0, lambda: log.on_span(tr, 0.0, 6.0, fun_name="a")),
    ])
    # b lies inside a's interval on ANOTHER thread: a does not cover it
    by_name = {r.fun_name: r for r in log.records}
    assert set(by_name) == {"a", "jit_b"}
    assert by_name["a"].self_s == pytest.approx(4.0)
    assert by_name["a"].covered == 1
    assert by_name["jit_b"].self_s == pytest.approx(2.0)
    assert by_name["a"].thread != by_name["jit_b"].thread
    assert log.totals()["seconds"] == pytest.approx(
        {"trace": 6.0, "lower": 2.0, "backend": 0.0})


@pytest.mark.parametrize("case", ["never-seen-open", "never-closed"])
def test_a_span_half_seen_is_taken_as_it_comes(case):
    log = CompileLog()
    tr = EVENT["trace"]
    if case == "never-seen-open":       # installed while it was open
        log.on_span(tr, 0.0, 2.0, fun_name="f")
        want = 2.0
    else:                               # opened above f, lost at exit
        log.on_start(tr, 0.0, fun_name="f")
        log.on_start(tr, 1.0, fun_name="lost")
        log.on_span(tr, 0.0, 3.0, fun_name="f")
        want = 3.0
    assert [(r.fun_name, r.self_s) for r in log.records] == [("f", want)]
    assert not log._open


@pytest.mark.parametrize("case", ["outermost", "covered"])
def test_the_ring_is_bounded(case):
    log = CompileLog(ring=4)
    if case == "outermost":
        _feed(log, [("trace", f"f{i}", float(i), i + 0.5)
                    for i in range(10)])
        assert [r.fun_name for r in log.records] == ["f6", "f7", "f8", "f9"]
    else:       # ten children cannot push their parent, or anyone, out
        _feed(log, [("trace", "first", 0.0, 1.0),
                    ("trace", "parent", 1.0, 12.0)]
              + [("trace", f"c{i}", 1.5 + i, 2.0 + i) for i in range(10)])
        assert [(r.fun_name, r.covered) for r in log.records] == [
            ("first", 0), ("parent", 10)]
    assert log.totals()["records"]["trace"] == 10 + 2 * (case == "covered")
    assert log.totals()["seconds"]["trace"] == pytest.approx(
        5.0 if case == "outermost" else 12.0)


def test_events_that_are_not_the_log_s_are_ignored():
    log = CompileLog()
    log.on_start("/jax/some/other_scalar", 1.0)
    log.on_span("/jax/some/other_span", 0.0, 1.0)
    log.on_event("/jax/compilation_cache/tasks_using_cache")
    log.on_duration("/jax/core/compile/backend_compile_duration", 1.0)
    assert not log.records and not log._open
    assert log.totals() == {"seconds": {"trace": 0.0, "lower": 0.0,
                                        "backend": 0.0},
                            "records": {"trace": 0, "lower": 0, "backend": 0},
                            "requests": 0, "hits": 0, "misses": 0}


# --------------------------------------------------- the persistent cache

@pytest.mark.parametrize("which", ["first-is-a-miss", "second-is-a-hit"])
def test_the_cache_s_answer_stands_on_the_record_and_in_the_counters(which):
    """On the process's own log, the one that ticks the registry."""
    log = compile_log.install()
    c = _unique()
    x = np.ones(8, np.float32)
    began, before = time.time(), log.totals()
    with obs.metrics_scope() as reg:
        jax.block_until_ready(_fresh(c)(x))
        if which == "second-is-a-hit":
            with obs.metrics_scope() as reg:
                jax.block_until_ready(_fresh(c)(x))
    backend = [r for r in log.records if r.stage == "backend"
               and r.fun_name == "jit(obs39_fresh)" and r.start >= began]
    after = log.totals()
    moved = tuple(after[k] - before[k]
                  for k in ("requests", "hits", "misses"))
    if which == "first-is-a-miss":
        assert [r.cache for r in backend] == ["miss"]
        assert backend[0].cache_retrieval_s is None
        assert moved == (1, 0, 1)
        want = {"compile_requests": 1, "compile_cache_misses": 1}
    else:
        assert [r.cache for r in backend] == ["miss", "hit"]
        assert backend[1].cache_retrieval_s >= 0.0
        assert backend[1].compile_saved_s is not None
        assert moved == (2, 1, 1)
        want = {"compile_requests": 1, "compile_cache_hits": 1}
    got = {name: v for (name, _labels), v in reg.counters().items()
           if name.startswith("compile_") and name != "compile_seconds"}
    assert got == want
    ticked = {dict(labels)["stage"]: v for (_n, labels), v
              in reg.counters("compile_seconds").items()}
    assert set(ticked) == {"trace", "lower", "backend"}
    assert all(v >= 0.0 for v in ticked.values())


# ------------------------------------------------------------ installing

def test_install_twice_registers_once():
    from jax._src import monitoring as registry
    first = compile_log.install()
    assert compile_log.install() is first is compile_log.LOG
    from elemental_tpu.core.compile_cache import enable_compile_cache
    enable_compile_cache()
    for listeners, mine in [
            (registry.get_scalar_listeners(), first.on_start),
            (registry.get_event_time_span_listeners(), first.on_span),
            (registry.get_event_listeners(), first.on_event),
            (registry.get_event_duration_listeners(), first.on_duration)]:
        assert listeners.count(mine) == 1


# ------------------------------------------- spans of an active Tracer

def test_a_compile_inside_a_span_is_that_span_s_child():
    x = np.ones(8, np.float32)
    tracer = obs.Tracer(metrics=False)
    with tracer:
        with tracer.span("x") as outer:
            jax.block_until_ready(_fresh(_unique())(x))
    mine = [s for s in tracer.spans if s.name.startswith("compile/")
            and "obs39_fresh" in s.attrs["fun_name"]]
    assert [s.name for s in mine] == ["compile/trace", "compile/lower",
                                      "compile/backend"]
    for s in mine:
        assert s.attrs["parent"] == "x" and s.depth == outer.depth + 1
        # on the tracer's clock, inside x (the anchor's two clock reads
        # are apart by less than a millisecond)
        assert outer.t0 - 1e-3 <= s.t0 <= s.t1 <= outer.t1 + 1e-3
        assert s.thread == outer.thread
    assert mine[2].attrs["cache"] == "miss"
    assert {s.attrs["cache"] for s in mine[:2]} == {""}
    # nested compile spans hang under the compile span they lie in
    inner = _fresh(_unique())
    with tracer:
        with tracer.span("y") as outer:
            jax.jit(lambda v: inner(v) + 1.0).lower(x)
    nested = [s for s in tracer.spans if s.name == "compile/trace"
              and s.attrs["fun_name"] == "obs39_fresh"
              and s.attrs["parent"] == "compile/trace"]
    assert len(nested) == 1 and nested[0].depth == outer.depth + 2


def test_the_exported_document_carries_the_spans_and_the_epoch_anchor():
    tracer = obs.Tracer(metrics=False)
    before = time.time_ns()
    with tracer:
        with tracer.span("x"):
            jax.block_until_ready(_fresh(_unique())(np.ones(8, np.float32)))
    after = time.time_ns()
    doc = obs.chrome_trace_doc(tracer, mode="test")
    anchor = doc["otherData"]["epoch_anchor"]
    assert set(anchor) == {"epoch_ns", "ts_us"}
    assert before <= anchor["epoch_ns"] <= after
    events = {ev["name"]: ev for ev in doc["traceEvents"] if ev["ph"] == "X"
              and "obs39_fresh" in ev["args"].get("fun_name", "obs39_fresh")}
    x = events["x"]
    # the anchor is an instant of the document's own time line
    assert anchor["ts_us"] <= x["ts"] + 1e3
    for name in ("compile/trace", "compile/lower", "compile/backend"):
        ev = events[name]
        assert ev["args"]["parent"] == "x"
        assert ev["args"]["cache"] == ("miss" if name.endswith("backend")
                                       else "")
        assert x["ts"] - 1e3 <= ev["ts"]
        assert ev["ts"] + ev["dur"] <= x["ts"] + x["dur"] + 1e3


@pytest.mark.parametrize("case", ["no-tracer", "tracer-not-active"])
def test_outside_an_active_tracer_the_log_makes_no_span(case):
    tracer = obs.Tracer(metrics=False)
    if case == "tracer-not-active":
        with tracer:
            pass
    assert obs.active_tracer() is None
    with tracer.span("x"):          # a span, but no ``with tracer:``
        jax.block_until_ready(_fresh(_unique())(np.ones(8, np.float32)))
    assert [s.name for s in tracer.spans] == ["x"]
    if case == "no-tracer":
        assert tracer.epoch_anchor is None
        assert "epoch_anchor" not in obs.chrome_trace_doc(tracer)["otherData"]


# ------------------------------------- the operator's view of a trace

def _lu_operands(n):
    rng = np.random.default_rng(39)
    grid = el.Grid(list(jax.devices()[:1]))
    A = el.from_global((rng.normal(size=(n, n)) + n * np.eye(n)).astype(
        np.float32), el.MC, el.MR, grid=grid)
    B = el.from_global(rng.normal(size=(n, 1)).astype(np.float32),
                       el.MC, el.MR, grid=grid)
    return A, B


def test_tick_channels_say_whose_python_a_trace_is():
    """With a ``Tracer`` active around ``jit(el.lu_solve).lower()`` the
    tick channels record host seconds per (driver, phase, step) WHILE
    TRACING (``block_until_ready`` passes tracers through): the view of
    LU's trace seconds by phase, inside the outer ``compile/trace``."""
    A, B = _lu_operands(256)

    def obs39_lu_solve(A, B):
        return el.lu_solve(A, B, nb=64)
    tracer = obs.Tracer(metrics=False)
    with tracer:
        with tracer.span("lower"):
            jax.jit(obs39_lu_solve).lower(A, B)
    (outer,) = [s for s in tracer.spans if s.name == "compile/trace"
                and s.attrs["fun_name"] == "obs39_lu_solve"]
    drivers = {r.driver for r in tracer.phases}
    assert {"lu", "trsm"} <= drivers
    steps = {r.step for r in tracer.phases if r.driver == "lu"}
    assert steps == set(range(256 // 64))
    assert {r.phase for r in tracer.phases if r.driver == "lu"} == {
        "panel", "swap", "solve", "update"}
    for r in tracer.phases:
        assert outer.t0 - 1e-3 <= r.t0 <= r.t1 <= outer.t1 + 1e-3
    assert sum(r.seconds for r in tracer.phases) <= (outer.t1 - outer.t0)


# -------------------------------------------- what a profiler sees

def _xplane():
    path = os.path.join(os.path.dirname(__file__), "..", "..", "benchmark",
                        "xplane.py")
    spec = importlib.util.spec_from_file_location("benchmark_xplane", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_tracer_spans_and_phases_stand_in_the_profiler_s_host_plane():
    xplane = _xplane()
    A, B = _lu_operands(128)
    trace_dir = tempfile.mkdtemp(prefix="obs39_trace_")
    try:
        jax.profiler.start_trace(trace_dir)
        try:
            with obs.Tracer(metrics=False) as tracer:
                with tracer.span("obs39/host-span"):
                    jax.block_until_ready(el.lu(A, nb=64)[0].local)
        finally:
            jax.profiler.stop_trace()
        planes = xplane.read_xplane(xplane.find_xplane(trace_dir))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    host = {name for plane, lines in planes.items()
            if plane.startswith("/host:")
            for events in lines.values() for name, _start, _dur in events}
    assert "obs39/host-span" in host
    assert {"lu/k00/panel", "lu/k01/panel", "lu/k00/update"} <= host
