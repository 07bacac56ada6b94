"""``mixed_solve``: unpivoted LU with bfloat16 update operands, refined on
the device (ISSUE 45).

The whole ``jit(mixed_solve)`` on 1x1 and on the 2x2 CPU mesh against
float64 numpy and against the benchmark reference's plain ``jax.numpy``
implementation of the same semantics, on the reference's seeded shifted
operand: the refined answer at the float32 level, the unrefined one at
least 100 times worse, the step count, a ``perm``-free factor, an operand
that needs pivoting reported through ``info``, the counters, the scopes
the compiled program carries and how ``benchmark/scopes.py`` classes them.
And the diagonal blocks' two lowerings (ISSUE 46): with the rule patched
true the Pallas kernel (interpreted here) gives the XLA loop's factor and
answer, and ``lu_nopiv_diag{impl}`` says which ran.
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


def _program(nb=NB, **kw):
    """The jitted program of these keywords, named as the benchmark names
    it, so the scope tests' compile is the solves' (a compile-cache hit)."""
    def bench_solve(a, b):
        return el.mixed_solve(a, b, nb=nb, **kw)
    return jax.jit(bench_solve)


def _factor(grid_name, A, low=mixed.LOW, nb=NB):
    """The packed factor alone, as one array."""
    LU = jax.jit(lambda a: mixed.lu_nopiv(a, nb=nb, low=low))(
        _dist(_grid(grid_name), A))
    assert isinstance(LU, el.DistMatrix)
    return np.asarray(el.to_global(LU))


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

    def residual(low):
        LU = _factor(grid_name, A, low).astype(np.float64)
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


# ------------------------------- the diagonal blocks in VMEM (ISSUE 46)

#: the factor's block: at NB a diagonal block is under ``_lu_nopiv``'s
#: sub-block order, ONE launch; at N the one diagonal block is cut in two
#: sub-blocks (256 and 128) and ``_lu_nopiv``'s blocked outer loop runs
#: around the kernel
KERNEL_NBS = [NB, N]


def _through_the_kernel(monkeypatch):
    """What one TPU chip runs; the grid is the CPU's, so the kernel is
    interpreted.  (The choice is static under each new ``jit``: nothing
    traced for the XLA loop is handed back.)"""
    monkeypatch.setattr(mixed, "_diag_blocks_in_vmem", lambda A: True)


@pytest.mark.parametrize("nb", KERNEL_NBS)
def test_kernel_path_gives_the_xla_paths_factor(monkeypatch, nb):
    """One algorithm, two lowerings.  With float32 updates nothing else
    differs: the packed factors agree to 32 ulps of the diagonal's scale,
    2 sqrt(n) (a fused multiply-subtract may round once where the twin
    rounds twice; to the bit here, where it does not).  With bf16 updates a
    float32 ulp in a panel may flip an entry's bf16 rounding: one bf16 ulp
    of an entry of L21, which is under 2 / sqrt(n)."""
    A, _B = _operands()
    xla, xla_f32 = _factor("1x1", A, nb=nb), _factor("1x1", A, None, nb)
    _through_the_kernel(monkeypatch)
    with obs.metrics_scope() as counters:
        got, got_f32 = _factor("1x1", A, nb=nb), _factor("1x1", A, None, nb)
    assert counters.counter_value("lu_nopiv_diag", impl="kernel") \
        == 2 * (N // nb)
    assert counters.counter_value("lu_nopiv_diag", impl="xla") == 0
    assert np.abs(got_f32 - xla_f32).max() <= (
        32 * np.finfo(np.float32).eps * 2 * np.sqrt(N))
    assert np.abs(got - xla).max() <= 2.0 ** -8 * 2 / np.sqrt(N)
    L, U = np.tril(got_f32, -1) + np.eye(N), np.triu(got_f32)
    assert np.linalg.norm(L.astype(np.float64) @ U - A) \
        < 1e-6 * np.linalg.norm(A)


@pytest.mark.parametrize("nb", KERNEL_NBS)
def test_kernel_path_solves_as_the_xla_path_does(monkeypatch, nb):
    """The same ``steps`` and ``converged``, and the refined answer under
    the same limits against float64 numpy as the XLA path's
    (``test_refined_answer_is_at_the_float32_level``)."""
    A, B = _operands()
    X_xla, info_xla = _solve("1x1", A, B, nb=nb)
    _through_the_kernel(monkeypatch)
    X, info = _solve("1x1", A, B, nb=nb)
    want = np.linalg.solve(A.astype(np.float64), B.astype(np.float64))
    assert info["steps"] == info_xla["steps"]
    assert info["converged"] and info_xla["converged"]
    assert _backward_error(A, B, X) < 3 * max(
        _backward_error(A, B, np.linalg.solve(A, B)), 1e-8)
    assert np.linalg.norm(X - want) < 1e-6 * np.linalg.norm(want)
    assert np.linalg.norm(X - X_xla) < 1e-6 * np.linalg.norm(want)
    assert info["backward_error"] == pytest.approx(
        _backward_error(A, B, X), rel=0.5)


@pytest.mark.parametrize("grid_name", sorted(GRIDS))
def test_diag_counter_says_which_lowering_ran(monkeypatch, grid_name):
    """One tick a diagonal block at trace time, beside ``lu_nopiv_step``:
    all ``xla`` on the CPU; all ``kernel`` where the rule says so, which it
    is asked on one chip only (the grid loop has the XLA loop alone)."""
    A, B = _operands()
    grid = _grid(grid_name)
    xla, kernel = {"kernel": 0, "xla": N // NB}, {"kernel": N // NB, "xla": 0}

    def ticks():
        with obs.metrics_scope() as counters:
            jax.jit(lambda a, b: el.mixed_solve(a, b, nb=NB)[0].local
                    ).lower(_dist(grid, A), _dist(grid, B))
        assert counters.counter_value("lu_nopiv_step") == N // NB
        return {impl: counters.counter_value("lu_nopiv_diag", impl=impl)
                for impl in ("kernel", "xla")}
    assert ticks() == xla
    _through_the_kernel(monkeypatch)
    assert ticks() == (kernel if grid_name == "1x1" else xla)


@pytest.mark.parametrize("chips,platform,dtype,want", [
    (1, "tpu", jnp.float32, True),
    (4, "tpu", jnp.float32, False),     # the grid loop: never timed
    (1, "cpu", jnp.float32, False),     # an interpreted kernel a sub-block
    (4, "cpu", jnp.float32, False),
    (1, "tpu", jnp.float64, False),     # Mosaic has no 64-bit type
    (1, "tpu", jnp.complex64, False),   # nor a complex one
    (1, "tpu", jnp.bfloat16, False)])   # the high side is float32
def test_the_rule_reads_the_grid_the_chip_and_the_dtype(chips, platform,
                                                        dtype, want):
    from types import SimpleNamespace as NS
    A = NS(grid=NS(size=chips, devices=[NS(platform=platform)] * chips),
           dtype=jnp.dtype(dtype))
    assert mixed._diag_blocks_in_vmem(A) is want


@pytest.mark.parametrize("dtype", [np.float64, np.complex64])
def test_what_the_kernel_cannot_hold_keeps_the_xla_loop(dtype):
    """float64 and complex operands factor as before (the rule says no on
    any chip): ``L U = A`` at the operand's own precision, no kernel."""
    A, _B = _operands()
    A = A.astype(dtype)
    with obs.metrics_scope() as counters:
        LU = np.asarray(el.to_global(jax.jit(
            lambda a: mixed.lu_nopiv(a, nb=NB, low=None))(
                _dist(_grid("1x1"), A))))
    assert counters.counter_value("lu_nopiv_diag", impl="kernel") == 0
    L, U = np.tril(LU, -1) + np.eye(N), np.triu(LU)
    assert np.linalg.norm(L @ U - A) < 50 * np.finfo(dtype).eps \
        * np.linalg.norm(A)


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
