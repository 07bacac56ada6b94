"""``mixed_solve``: unpivoted LU with bfloat16 update operands, refined on
the device (ISSUE 45).

The whole ``jit(mixed_solve)`` on 1x1 and on the 2x2 CPU mesh against
float64 numpy and against the benchmark reference's plain ``jax.numpy``
implementation of the same semantics, on the reference's seeded shifted
operand: the refined answer at the float32 level, the unrefined one at
least 100 times worse, the step count, a ``perm``-free factor, an operand
that needs pivoting reported through ``info``, the counters, the scopes
the compiled program carries and how ``benchmark/scopes.py`` classes them.
"""
import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import elemental_tpu as el
from elemental_tpu import obs

from ..obs.test_scopes import op_names, stripped
from .test_least_squares_tall import _bench_module

mixed = importlib.import_module("elemental_tpu.lapack.mixed")

N, NB = 384, 128
GRIDS = {"1x1": (1, 1), "2x2": (2, 2)}


def _grid(name):
    r, c = GRIDS[name]
    return el.Grid(list(jax.devices()[:r * c]), height=r)


def _operands(n=N, nrhs=1, seed=0, operand="shifted_pm1"):
    """The benchmark's A and b as float32 arrays."""
    reference = _bench_module("reference")
    entries = {**reference.ENTRIES,
               "shifted_pm1": _bench_module("reference_mxp").entry_shifted_pm1}
    ka = np.uint32(reference.operand_key(seed, 0, 0))
    kb = np.uint32(reference.operand_key(seed, 0, 1))
    A = reference.plain_block(entries[operand](n, ka), 0, n, n)
    B = reference.plain_block(reference.entry_uniform_pm1(n, kb), 0, n, nrhs)
    return np.asarray(A, np.float32), np.asarray(B, np.float32)


def _dist(grid, F):
    return el.from_global(F, el.MC, el.MR, grid=grid)


def _backward_error(A, B, X):
    A, B, X = (np.asarray(M, np.float64) for M in (A, B, X))
    return np.linalg.norm(B - A @ X) / (
        np.linalg.norm(A) * np.linalg.norm(X) + np.linalg.norm(B))


def _program(**kw):
    """The jitted program of these keywords, named as the benchmark names
    it, so the scope tests' compile is the solves' (a compile-cache hit)."""
    def bench_solve(a, b):
        return el.mixed_solve(a, b, nb=NB, **kw)
    return jax.jit(bench_solve)


def _solve(grid_name, A, B, **kw):
    grid = _grid(grid_name)
    X, info = _program(**kw)(_dist(grid, A), _dist(grid, B))
    return np.asarray(el.to_global(X)), {k: np.asarray(v).item()
                                         for k, v in info.items()}


@pytest.fixture(scope="module")
def solved():
    """{grid: (refined X, info, unrefined X, info)} on one operand."""
    A, B = _operands()
    return A, B, {g: _solve(g, A, B) + _solve(g, A, B, max_steps=0)
                  for g in GRIDS}


@pytest.mark.parametrize("grid_name", sorted(GRIDS))
def test_refined_answer_is_at_the_float32_level(solved, grid_name):
    """Against float64 numpy: the refined X reads what a float32 direct
    solve reads, the unrefined one at least 100 times worse; the program's
    own estimate agrees with both; at least one step ran."""
    A, B, out = solved
    X, info, X0, info0 = out[grid_name]
    want = np.linalg.solve(A.astype(np.float64), B.astype(np.float64))
    direct = np.linalg.solve(A, B)                   # float32 LAPACK
    refined, unrefined = _backward_error(A, B, X), _backward_error(A, B, X0)
    assert refined < 3 * max(_backward_error(A, B, direct), 1e-8)
    assert unrefined > 100 * refined
    assert np.linalg.norm(X - want) < 1e-6 * np.linalg.norm(want)
    assert np.linalg.norm(X0 - want) > 1e-5 * np.linalg.norm(want)
    assert 1 <= info["steps"] <= mixed.MAX_STEPS and info["converged"]
    assert info0["steps"] == 0 and not info0["converged"]
    assert info["backward_error"] == pytest.approx(refined, rel=0.5)
    assert info0["backward_error"] == pytest.approx(unrefined, rel=0.05)


@pytest.mark.parametrize("grid_name", sorted(GRIDS))
def test_agrees_with_the_plain_reference(solved, grid_name):
    """The benchmark's plain implementation (unblocked unpivoted LU, every
    rank-1 update with bf16-rounded operands, refinement with a float32
    residual): refined, the two agree at the float32 level; unrefined,
    both are wrong by the bf16 level, the plain one (which rounds inside
    a panel too) the more."""
    A, B, out = solved
    X, info, X0, _info0 = out[grid_name]
    plain = jax.jit(_bench_module("reference_mxp").plain_mixed_solve,
                    static_argnums=2)
    P, P0 = np.asarray(plain(A, B, 2)), np.asarray(plain(A, B, 0))
    scale = np.linalg.norm(P)
    assert np.linalg.norm(X - P) < 1e-6 * scale
    assert _backward_error(A, B, P) < 2e-8
    assert 1e-6 * scale < np.linalg.norm(X0 - P) < 1e-3 * scale
    assert _backward_error(A, B, P0) > 0.5 * _backward_error(A, B, X0)


@pytest.mark.parametrize("grid_name", sorted(GRIDS))
def test_factor_has_no_permutation(grid_name):
    """``L U = A`` with no ``perm``: to the bf16 level with rounded update
    operands, to the float32 level without; and the bf16 factor is NOT at
    the float32 level (the rounding is really there on the CPU)."""
    A, _B = _operands()
    grid = _grid(grid_name)

    def residual(low):
        LU = jax.jit(lambda a: mixed.lu_nopiv(a, nb=NB, low=low))(
            _dist(grid, A))
        assert isinstance(LU, el.DistMatrix)
        LU = np.asarray(el.to_global(LU), np.float64)
        L, U = np.tril(LU, -1) + np.eye(N), np.triu(LU)
        return np.linalg.norm(L @ U - A) / np.linalg.norm(A)
    low, high = residual(jnp.bfloat16), residual(None)
    assert 1e-5 < low < 2e-3
    assert high < 1e-6


@pytest.mark.parametrize("grid_name", sorted(GRIDS))
def test_an_operand_that_needs_pivoting_is_reported(grid_name):
    """``uniform_pm1`` has pivots near zero without row exchanges: the
    answer is not at the float32 level and ``info`` says so."""
    A, B = _operands(operand="uniform_pm1")
    X, info = _solve(grid_name, A, B)
    assert not info["converged"]
    berr = _backward_error(A, B, X)
    assert not berr < 1e-6                            # NaN counts
    pivoted = np.linalg.solve(A, B)
    assert _backward_error(A, B, pivoted) < 1e-6


def test_more_right_hand_sides_and_a_ragged_last_panel():
    """n not a multiple of nb, eight right-hand sides, 2x2."""
    n = 328
    A, B = _operands(n=n, nrhs=8, seed=3)
    X, info = _solve("2x2", A, B)
    assert X.shape == (n, 8)
    assert _backward_error(A, B, X) < 2e-8 and info["converged"]


def test_counters_tick_once_a_step_and_name_the_update_dtype():
    A, B = _operands()
    for grid_name in GRIDS:
        grid = _grid(grid_name)
        with obs.metrics_scope() as counters:
            jax.jit(lambda a, b: el.mixed_solve(a, b, nb=NB)[0].local
                    ).lower(_dist(grid, A), _dist(grid, B))
        assert counters.counter_value("lu_nopiv_step") == N // NB
        assert counters.counter_value(
            "mixed_update", dtype="bfloat16") == N // NB - 1
        assert counters.counter_value("mixed_update", dtype="float32") == 0
        with obs.metrics_scope() as counters:
            jax.jit(lambda a: mixed.lu_nopiv(a, nb=NB, low=None).local
                    ).lower(_dist(grid, A))
        assert counters.counter_value(
            "mixed_update", dtype="float32") == N // NB - 1
        assert counters.counter_value("mixed_update", dtype="bfloat16") == 0


# ------------------------------------------------- the compiled program

def _compiled(grid_name, **kw):
    grid = _grid(grid_name)
    A, B = _operands()
    return _program(**kw).lower(
        _dist(grid, A), _dist(grid, B)).compile().as_text()


@pytest.fixture(scope="module")
def texts():
    return {g: _compiled(g) for g in GRIDS}


@pytest.mark.parametrize("grid_name", sorted(GRIDS))
def test_compiled_program_carries_every_scope(texts, grid_name):
    """One program: the factor's phases under ``factor``, the first
    solve's sweeps under ``sweeps``, the refinement's two phases INSIDE
    the loop's body and classed as the refinement's, not as ``sweep``,
    ``update`` or ``panel``."""
    names = op_names(texts[grid_name])
    scopes = _bench_module("scopes")
    found = {scopes.classify(n) for n in names}
    phases = ("diag", "panel", "update") if grid_name == "1x1" \
        else ("panel", "solve", "update")
    for phase in phases:
        pattern = re.compile(
            rf"/el\.mixed_solve/factor/el\.lu_nopiv/k\d\d/{phase}(/|$)")
        mine = [n for n in names if pattern.search(n)]
        assert mine, phase
        assert {scopes.classify(n) for n in mine
                if "el.redist." not in n} == {
            (phase, f"lu_nopiv/{phase}")}, phase
    sweeps = [n for n in names
              if "/el.mixed_solve/sweeps/el.trsm/" in n
              and "el.redist." not in n]
    assert sweeps and {scopes.classify(n)[0] for n in sweeps} == {
        scopes.SWEEP}
    for phase, inner in (("correct", "el.trsm"), ("residual", "el.gemm")):
        body = [n for n in names if re.search(
            rf"/el\.mixed_solve/el\.refine/while/body/k01/{phase}/{inner}/",
            n) and "el.redist." not in n]
        assert body, phase
        assert {scopes.classify(n) for n in body} == {
            (phase, f"refine/{phase}")}, phase
    first = [n for n in names
             if "/el.mixed_solve/el.refine/k00/residual/" in n]
    assert first
    # no op of the loop is booked as a sweep, and none of the factor's
    assert not any(scopes.classify(n)[0] == scopes.SWEEP
                   for n in names if "/el.refine/" in n)
    assert {"residual", "correct"} <= set(obs.PHASES)
    # one while loop carries the refinement; its condition is the
    # program's own (no host loop, no to_global)
    assert any(n.endswith("/el.refine/while") for n in names)


def test_the_low_side_is_bfloat16_on_every_backend(texts):
    """The update's operands are ROUNDED in the program (a convert to
    bf16), whatever ``precision=`` means to the backend's float32 dots."""
    for text in texts.values():
        assert re.search(r"bf16\[[\d,]+\][^ ]* convert\(", text) \
            or re.search(r"= bf16\[", text)


def test_precision_is_the_high_side(texts):
    """``precision`` reaches panels, sweeps and residual: at HIGH the
    optimized program differs from the default's, which is HIGHEST's."""
    default = texts["1x1"]
    highest = _compiled("1x1", precision=jax.lax.Precision.HIGHEST)
    high = _compiled("1x1", precision=jax.lax.Precision.HIGH)
    assert stripped(default) == stripped(highest)
    dots = re.findall(r"operand_precision=\{(\w+),(\w+)\}", high)
    if dots:                                  # a backend that prints them
        assert stripped(default) != stripped(high)
        assert "highest" not in {p for pair in dots for p in pair}


def test_no_chip_gathers_the_operand(texts):
    """On 2x2 every array of the optimized program has at most the entries
    of the largest panel gathered to a chip (n x nb), a quarter of A here:
    nothing [STAR,STAR] of the operand's size."""
    sizes = [int(np.prod([int(d) for d in dims.split(",")]))
             for dims in re.findall(r"\b(?:f32|bf16)\[([\d,]+)\]",
                                    texts["2x2"])]
    assert max(sizes) <= max(N * NB, N * N // 4)
