"""The phase primitive in both modes (ISSUE 28).

Under ``jit`` every driver's phases reach the optimized HLO as
``op_name`` scopes (grammar in ``elemental_tpu/obs/__init__.py``), they
cost the compiled program nothing (with ``jax.named_scope`` patched to a
null context the HLO is the same once metadata is stripped), and an
eager run with a ``PhaseTimer`` still ticks the sequence the tick
protocol gave (pinned from the parent commit in
``phase_records_pinned.json``)."""
import contextlib
import functools
import json
import os
import re

import jax
import numpy as np
import pytest

import elemental_tpu as el
from elemental_tpu import obs

N, NB = 256, 64

#: driver -> (the phases it declares on 1x1, on 2x2)
PHASES = {
    "cholesky": ({"diag", "panel", "update"},
                 {"diag", "panel", "spread", "update"}),
    "lu": ({"panel", "swap", "solve", "update"},) * 2,
    "qr": ({"panel", "update"},) * 2,
    "gemm": ({"panel"},) * 2,
    "herk": ({"spread", "update"},) * 2,
    "trsm": ({"solve", "update"},) * 2,
}


def _grid(name):
    return el.Grid(list(jax.devices()[:1 if name == "1x1" else 4]))


def _operands(op, grid):
    rng = np.random.default_rng(0)
    F = rng.normal(size=(N, N)).astype(np.float32)
    if op in ("cholesky", "hpd_solve"):
        F = F @ F.T + N * np.eye(N, dtype=np.float32)
    if op == "trsm":
        F = np.tril(F) + N * np.eye(N, dtype=np.float32)
    A = el.from_global(F, el.MC, el.MR, grid=grid)
    B = el.from_global(rng.normal(size=(N, 8)).astype(np.float32),
                       el.MC, el.MR, grid=grid)
    return A, B


#: op -> the jitted call, drivers with the crossover off so that the
#: distributed phases run (the public solves keep their default: tail)
CALLS = {
    "hpd_solve": lambda A, B: el.hpd_solve(A, B, nb=NB),
    "lu_solve": lambda A, B: el.lu_solve(A, B, nb=NB),
    "cholesky": lambda A, B: el.cholesky(A, nb=NB, crossover=0),
    "lu": lambda A, B: el.lu(A, nb=NB, crossover=0),
    "qr": lambda A, B: el.qr(A, nb=NB),
    "gemm": lambda A, B: el.gemm(A, A, alg="C", nb=NB),
    "herk": lambda A, B: el.herk("L", A, nb=NB),
    "trsm": lambda A, B: el.trsm("L", "L", "N", A, B, nb=NB),
}


def _compile(op, grid_name):
    A, B = _operands(op, _grid(grid_name))

    def bench_solve(A, B):
        return CALLS[op](A, B)
    return jax.jit(bench_solve).lower(A, B).compile().as_text()


@functools.lru_cache(maxsize=None)
def compiled_text(op, grid_name):
    return _compile(op, grid_name)


def op_names(text):
    return set(re.findall(r'op_name="([^"]*)"', text))


def stripped(text):
    """The optimized HLO without what scopes may touch: each op's
    ``metadata={...}`` and the file / function / stack-frame tables
    between the module line and the first computation."""
    text = re.sub(r",? ?metadata=\{[^}]*\}", "", text)
    head, _, rest = text.partition("\n")
    body = rest[min(i for i in (rest.find("\n%"), rest.find("\nENTRY"))
                    if i >= 0):]
    return head + body


GRIDS = ["1x1", "2x2"]


@pytest.mark.parametrize("grid_name", GRIDS)
@pytest.mark.parametrize("driver", sorted(PHASES))
def test_compiled_driver_carries_every_phase(driver, grid_name):
    names = op_names(compiled_text(driver, grid_name))
    for phase in PHASES[driver][GRIDS.index(grid_name)]:
        pattern = re.compile(rf"/el\.{driver}/k\d\d+/{phase}(/|$)")
        assert any(pattern.search(n) for n in names), (driver, phase)
    redist = [n for n in names if "/el.redist." in n]
    if grid_name == "2x2":
        assert redist, f"no el.redist. scope in {driver} on 2x2"
    for n in redist:
        assert re.search(r"/el\.redist\.(panel_spread|row_permute|"
                         r"[A-Z]+_[A-Z]+\.to\.[A-Z]+_[A-Z]+)(/|$)", n), n


@pytest.mark.parametrize("grid_name", GRIDS)
@pytest.mark.parametrize("solve,factor,phases", [
    ("hpd_solve", "cholesky", ({"diag", "panel", "update"},
                               {"diag", "panel", "spread", "update",
                                "tail"})),
    ("lu_solve", "lu", ({"panel", "swap", "solve", "update"},
                        {"panel", "swap", "solve", "update", "tail"}))])
def test_compiled_solve_opens_factor_and_sweeps(solve, factor, phases,
                                                grid_name):
    names = op_names(compiled_text(solve, grid_name))
    for phase in phases[GRIDS.index(grid_name)]:
        pattern = re.compile(
            rf"/el\.{solve}/factor/el\.{factor}/k\d\d+/{phase}(/|$)")
        assert any(pattern.search(n) for n in names), (solve, phase)
    for phase in ("solve", "update"):
        pattern = re.compile(
            rf"/el\.{solve}/sweeps/el\.trsm/k\d\d+/{phase}(/|$)")
        assert any(pattern.search(n) for n in names), (solve, phase)
    assert not any(re.search(r"/factor/.*el\.trsm/", n) for n in names)
    if grid_name == "2x2":
        assert any("/el.redist." in n for n in names)
    if solve == "lu_solve":
        assert any("/sweeps/el.redist.row_permute" in n for n in names)


@pytest.mark.parametrize("grid_name", GRIDS)
@pytest.mark.parametrize("op", sorted(CALLS))
def test_scopes_cost_the_compiled_program_nothing(op, grid_name,
                                                  monkeypatch):
    """With ``jax.named_scope`` a null context the optimized HLO is the
    same text, metadata and stack-frame tables apart."""
    with_scopes = compiled_text(op, grid_name)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    without = _compile(op, grid_name)
    assert not any("el." in n for n in op_names(without))
    assert any("/el." in n for n in op_names(with_scopes))
    assert stripped(with_scopes) == stripped(without)


def renamed(text):
    """A stripped text with every ``%name`` replaced by its rank of first
    appearance.  XLA names an instruction it MERGES after the tail of the
    merged ``op_name`` (two transposes under ``shard_map`` make
    ``%transpose_transpose.4``; under ``shard_map/unpack`` the tail is
    ``unpack/transpose;unpack/transpose`` and the name ``%transpose.13``),
    so a scope opened inside a ``shard_map`` renames such instructions and
    moves the numbers of those made after them.  Ops, shapes, operands and
    order are compared."""
    seen = {}
    return re.sub(r"%[\w.\-]+",
                  lambda m: seen.setdefault(m.group(0), f"%n{len(seen)}"),
                  text)


def _panel(A):
    return el.redistribute(A, el.VC, el.STAR)


#: exchanges by the engine's routes: the parts are opened INSIDE the
#: engine's jitted entries, whose traces jax caches by the function
PART_CALLS = {
    "to_v": lambda A, B: el.redistribute(A, el.VC, el.STAR),
    "from_v": lambda A, B: el.redistribute(_panel(A), el.MC, el.MR),
    "to_star_v": lambda A, B: el.redistribute(A, el.STAR, el.VR),
    "gather": lambda A, B: el.redistribute(A, el.STAR, el.STAR),
    "chain": lambda A, B: el.redistribute(A, el.MR, el.STAR),
    "ladder": lambda A, B: el.redistribute(_panel(B), el.MC, el.STAR),
    "filter": lambda A, B: el.redistribute(
        el.redistribute(B, el.STAR, el.STAR), el.MC, el.MR),
    "bf16": lambda A, B: el.redistribute(A, el.VC, el.STAR,
                                         comm_precision="bf16"),
    "int8": lambda A, B: el.redistribute(A, el.STAR, el.STAR,
                                         comm_precision="int8"),
    "direct": lambda A, B: el.redistribute(A, el.VC, el.STAR,
                                           path="direct"),
    "panel_spread": lambda A, B: el.panel_spread(_panel(B)),
    "panel_spread.int8": lambda A, B: el.panel_spread(
        _panel(B), comm_precision="int8"),
}


@pytest.mark.parametrize("call", sorted(PART_CALLS))
def test_part_scopes_cost_the_compiled_program_nothing(call, monkeypatch):
    """The parts of an exchange name ops and do nothing else: with the
    engine's entries traced anew under a null ``jax.named_scope`` (the
    helper ``obs.redist_part`` looks it up at call time) the optimized HLO
    is the same program, metadata apart and the instructions renamed in
    order."""
    from .test_metrics import _fresh_engine_jits
    A, B = _operands("gemm", _grid("2x2"))

    def text():
        def bench_solve(A, B):
            return PART_CALLS[call](A, B)
        with _fresh_engine_jits():
            return jax.jit(bench_solve).lower(A, B).compile().as_text()

    with_parts = text()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    without = text()
    parts = {seg for n in op_names(with_parts) if "/el.redist." in n
             for seg in n.split("/")} & set(obs.REDIST_PARTS)
    assert parts, "no part under the exchange's name"
    assert not any(seg in obs.REDIST_PARTS for n in op_names(without)
                   for seg in n.split("/"))
    assert renamed(stripped(with_parts)) == renamed(stripped(without))


def test_step_has_two_digits_or_more_and_nests():
    seen = []

    @contextlib.contextmanager
    def spy(name):
        seen.append(name)
        yield

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "named_scope", spy)
        with obs.NULL_HOOK.phase("panel", 3):
            with obs.NULL_HOOK.phase("tail", 117) as ph:
                ph.done()
    assert seen == ["k03/panel", "k117/tail"]


def test_scoped_form_ticks_on_exit_only_when_done():
    class Hook(obs.PhaseHook):
        def __init__(self):
            self.ticks = []

        def tick(self, phase, step, *arrays):
            self.ticks.append((phase, step, arrays))

    hook = Hook()
    with hook.phase("panel", 0):                 # naming only
        pass
    for k in range(2):
        with hook.phase("solve", k) as ph:       # left before done
            if k == 0:
                continue
            ph.done(1, 2)
            assert hook.ticks == []              # the tick is the exit's
    with pytest.raises(ZeroDivisionError):
        with hook.phase("update", 2) as ph:
            ph.done()
            1 / 0
    assert hook.ticks == [("solve", 1, (1, 2))]


def test_every_hook_a_driver_may_hold_offers_the_scoped_form():
    from elemental_tpu.obs.tracer import _Fanout
    from elemental_tpu.resilience.health import HealthMonitor, attach_health
    tr = obs.Tracer(metrics=False)
    hooks = [obs.NULL_HOOK, obs.PhaseTimer(), tr.channel("lu"),
             _Fanout((obs.PhaseTimer(),)), HealthMonitor(),
             attach_health("lu", True, obs.PhaseTimer())[0],
             attach_health("lu", True, obs.NULL_HOOK)[0]]
    for hook in hooks:
        assert isinstance(hook, obs.PhaseHook)
        with hook.phase("panel", 0) as ph:
            ph.done()


with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "phase_records_pinned.json")) as _f:
    PINNED = json.load(_f)


@pytest.mark.parametrize("case", sorted(PINNED))
def test_eager_phase_timer_document_is_unchanged(case):
    """``phase_timings/v1`` of an eager run: the (phase, step) sequence
    the parent's tick protocol gave, and the document's shape."""
    driver, grid_name, args = case.split(".", 2)
    kwargs = {k: json.loads(v.lower()) for k, v in
              (kv.split("=") for kv in args.split(",") if kv)}
    rng = np.random.default_rng(0)
    F = rng.normal(size=(N, N))
    if driver == "cholesky":
        F = F @ F.T + N * np.eye(N)
    A = el.from_global(F, el.MC, el.MR, grid=_grid(grid_name))
    timer = obs.PhaseTimer()
    getattr(el, driver)(A, nb=NB, timer=timer, **kwargs)
    assert [[r["phase"], r["step"]] for r in timer.records] == PINNED[case]
    doc = timer.report(driver=driver)
    assert doc["schema"] == "phase_timings/v1" and doc["driver"] == driver
    assert set(doc) == {"schema", "steps", "totals", "total_seconds",
                        "driver"}
    assert [s["step"] for s in doc["steps"]] == sorted(
        {step for _phase, step in PINNED[case]})
    assert set(doc["totals"]) == {phase for phase, _step in PINNED[case]}
    assert doc["total_seconds"] == pytest.approx(
        sum(doc["totals"].values()))
