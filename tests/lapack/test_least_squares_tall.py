"""The tall-skinny route of ``least_squares`` and ``tsqr`` (ISSUE 43).

The whole ``jit(least_squares)``, A donated, on 1x1 and three grids
against float64 ``numpy.linalg.lstsq`` on the benchmark reference's
seeded collinear, graded operand (the harder of its two; rows that need
not divide over the chips); the route counter; the scopes the compiled
program carries and how ``benchmark/scopes.py`` classifies them;
``precision`` reaching the products; and the proof that no chip holds
more of A than its own rows.

The shipped rule takes the route only at the aspect it was measured at on
the chip (8192 rows a column a chip); the tests reach it at sizes a CPU
holds by lowering that one constant (``_solve`` and ``_compiled`` do, for
the length of a trace), and ``test_route_rule`` reads the rule as shipped.
"""
import contextlib
import importlib
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import elemental_tpu as el
from elemental_tpu import obs

from ..obs.test_scopes import op_names, stripped

qr_mod = importlib.import_module("elemental_tpu.lapack.qr")

_BENCH = os.path.join(os.path.dirname(__file__), "..", "..", "benchmark")


def _bench_module(name):
    """A file of ``benchmark/``: plain Python, nothing of the program."""
    import sys
    sys.path.insert(0, _BENCH)
    try:
        spec = importlib.util.spec_from_file_location(
            f"benchmark_{name}", os.path.join(_BENCH, name + ".py"))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module
    finally:
        sys.path.remove(_BENCH)


GRIDS = {"1x1": (1, 1), "2x2": (2, 2), "2x4": (2, 4), "4x1": (4, 1)}
SHAPES = [(2048, 16, 8), (1000, 12, 1)]


def _grid(name):
    r, c = GRIDS[name]
    return el.Grid(list(jax.devices()[:r * c]), height=r)


def _operands(m, n, nrhs, seed=0, dtype=np.float64):
    """The benchmark's operand and responses as ordinary arrays."""
    reference = _bench_module("reference")
    lstsq = _bench_module("reference_lstsq")
    ka = np.uint32(reference.operand_key(seed, 0, 0))
    kb = np.uint32(reference.operand_key(seed, 0, 1))
    A = reference.plain_block(lstsq.entry_graded_collinear(n, ka), 0, m, n)
    B = reference.plain_block(reference.entry_uniform_pm1(n, kb), 0, m, nrhs)
    return np.asarray(A, dtype), np.asarray(B, dtype)


def _dist(grid, F):
    return el.from_global(F, el.MC, el.MR, grid=grid)


@contextlib.contextmanager
def _aspect(rows_a_column):
    """The rule's aspect lowered while a program is traced."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(qr_mod, "_TALL_ASPECT", rows_a_column)
        yield


def _solve(grid, F, B, **kw):
    """(X, counters) of the whole jitted driver, A donated."""
    def bench_solve(A, B):
        return el.least_squares(A, B, **kw)
    with obs.metrics_scope() as counters, _aspect(4):
        X = jax.jit(bench_solve, donate_argnums=0)(_dist(grid, F),
                                                    _dist(grid, B))
    return np.asarray(el.to_global(X)), counters


@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("grid_name", sorted(GRIDS))
def test_tall_route_matches_float64_lstsq(grid_name, shape):
    m, n, nrhs = shape
    F, B = _operands(m, n, nrhs)
    X, counters = _solve(_grid(grid_name), F, B)
    want, *_ = np.linalg.lstsq(F, B, rcond=None)
    # condition 6e7 as stored: eps cond = 1e-8 of the largest entry
    assert np.abs(X - want).max() <= 1e-7 * np.abs(want).max()
    R = B - F @ X
    angle = np.abs(F.T @ R) / (np.linalg.norm(F, axis=0)[:, None]
                               * np.linalg.norm(R, axis=0)[None, :])
    assert angle.max() < 1e-12
    p = GRIDS[grid_name][0] * GRIDS[grid_name][1]
    tall = int(p > 1)               # one chip keeps the blocked route
    assert counters.counter_value("lstsq_route", kind="tall") == tall
    assert counters.counter_value("lstsq_route", kind="blocked") == 1 - tall
    assert counters.counter_value("tsqr_leaf") == tall
    assert counters.counter_value("tsqr_tree_bytes") == tall * p * n * n * 8


@pytest.mark.parametrize("chunk", [96, 125, 1000])
def test_rows_are_summed_in_chunks(chunk, monkeypatch):
    """A chip's 250 rows below the head in chunks of 96 (three, the last
    padded), 125 (two) and in one: the same answer, each product over the
    rows a partial product a chunk summed by halves."""
    monkeypatch.setattr(qr_mod, "_TALL_CHUNK", chunk)
    F, B = _operands(1000, 12, 1)
    X, counters = _solve(_grid("2x2"), F, B)
    want, *_ = np.linalg.lstsq(F, B, rcond=None)
    assert np.abs(X - want).max() <= 1e-7 * np.abs(want).max()
    assert counters.counter_value("lstsq_route", kind="tall") == 1
    x = np.arange(5 * 3, dtype=np.float64).reshape(5, 3)
    np.testing.assert_array_equal(qr_mod._pair_sum(jnp.asarray(x)), x.sum(0))
    rows = jnp.asarray(np.arange(250 * 2, dtype=np.float64).reshape(250, 2))
    chunks = qr_mod._tall_chunks(rows)
    assert chunks.shape == (-(-250 // min(chunk, 250)), 2, min(chunk, 250))
    np.testing.assert_array_equal(qr_mod._tall_rows(chunks, 250), rows)


def test_operands_have_the_conditions_the_reference_states():
    """In float64 at the test size.  The collinear operand: about 2e8 as
    stored, about sqrt(n) 2^10 with the columns scaled to unit length.
    The cell's operand, graded only: the grading's 2^(255/16) = 6.3e4
    times a random matrix's (1 + sqrt(n/m)) / (1 - sqrt(n/m)), 1.29 at
    these 16384 rows and 1.011 at the cell's 8,388,608; that second
    factor alone once the columns are scaled."""
    F, _B = _operands(16384, 256, 1)
    s = np.linalg.svd(F, compute_uv=False)
    assert 1e8 < s[0] / s[-1] < 4e8
    s = np.linalg.svd(F / np.linalg.norm(F, axis=0), compute_uv=False)
    assert 1.2e4 < s[0] / s[-1] < 2.4e4
    reference = _bench_module("reference")
    lstsq = _bench_module("reference_lstsq")
    key = np.uint32(reference.operand_key(0, 0, 0))
    G = np.asarray(reference.plain_block(
        lstsq.entry_graded(256, key), 0, 16384, 256), np.float64)
    s = np.linalg.svd(G, compute_uv=False)
    assert 4e4 < s[0] / s[-1] < 9e4
    s = np.linalg.svd(G / np.linalg.norm(G, axis=0), compute_uv=False)
    assert 1.2 < s[0] / s[-1] < 1.4


def _meta(grid, m, n, dtype=np.float32):
    from elemental_tpu.core.distmatrix import DistMatrix
    return DistMatrix(jax.ShapeDtypeStruct((m, n), dtype), (m, n), el.MC,
                      el.MR, 0, 0, grid)


@pytest.mark.parametrize("why,tall,grid_name,m,n,nrhs,kw", [
    ("the cell's shape", True, "2x2", 8388608, 256, 8, {}),
    ("a slab under 8192 times its width", False, "2x2", 8388604, 256, 8, {}),
    ("the cell's aspect, narrower", True, "2x4", 8192 * 24 * 8, 24, 1, {}),
    ("one chip (chip_smoke's line)", False, "1x1", 65536, 512, 1, {}),
    ("one chip at the cell's aspect", False, "1x1", 2097152, 256, 8, {}),
    ("nearly square", False, "2x2", 64, 48, 2, {}),
    ("the parent's tests", False, "2x4", 40, 12, 3, {}),
    ("B wider than A", False, "2x2", 8192 * 8 * 4, 8, 16, {}),
    ("wider than was measured", False, "2x2", 8192 * 512 * 4, 512, 1, {}),
    ("checksum guard", False, "2x2", 8388608, 256, 8, {"abft": True}),
    ("complex entries", False, "2x2", 8388608, 256, 8,
     {"dtype": np.complex64}),
])
def test_route_rule(why, tall, grid_name, m, n, nrhs, kw):
    """The rule as shipped, on shapes: no array is made."""
    grid = _grid(grid_name)
    dtype = kw.pop("dtype", np.float32)
    assert qr_mod._takes_tall_route(
        _meta(grid, m, n, dtype), _meta(grid, m, nrhs, dtype),
        kw.get("abft")) is tall, why


def test_a_nearly_square_problem_keeps_the_blocked_route():
    rng = np.random.default_rng(3)
    F, B = rng.normal(size=(64, 48)), rng.normal(size=(64, 2))
    X, counters = _solve(_grid("2x2"), F, B, nb=16)
    want, *_ = np.linalg.lstsq(F, B, rcond=None)
    np.testing.assert_allclose(X, want, atol=1e-10)
    assert counters.counter_value("lstsq_route", kind="blocked") == 1
    assert counters.counter_value("lstsq_route", kind="tall") == 0
    assert counters.counter_value("tsqr_leaf") == 0


# ------------------------------------------------- the compiled program

M, N, NRHS = 2048, 16, 8


def _compiled(grid_name, dtype=jnp.float32, **kw):
    grid = _grid(grid_name)
    F, B = _operands(M, N, NRHS, dtype=np.dtype(dtype))

    def bench_solve(A, B):
        return el.least_squares(A, B, **kw)
    with _aspect(4):
        return jax.jit(bench_solve, donate_argnums=0).lower(
            _dist(grid, F), _dist(grid, B)).compile().as_text()


@pytest.fixture(scope="module")
def text22():
    return _compiled("2x2")


PHASES = ("local", "tree", "applyq", "solve")


def test_compiled_program_carries_every_scope(text22):
    names = op_names(text22)
    scopes = _bench_module("scopes")
    for phase in PHASES:
        pattern = re.compile(
            rf"/el\.least_squares/el\.tsqr/(.*/)?k00/{phase}(/|$)")
        mine = [n for n in names if pattern.search(n)]
        assert mine, phase
        assert {scopes.classify(n) for n in mine} == {
            (phase, f"tsqr/{phase}")}, phase
    assert set(PHASES) <= set(obs.PHASES)
    found = {scopes.classify(n) for n in names}
    assert (scopes.REDIST, "el.redist.MC_MR.to.VC_STAR") in found
    assert (scopes.REDIST, "el.redist.STAR_STAR.to.MC_MR") in found
    # nothing of the route is outside a phase or a hop
    assert not any(cls == scopes.UNSCOPED and "el." in n
                   for n in names for cls in [scopes.classify(n)[0]])
    # ... but what the compiler hoists to the shard_map's edge (constants)
    assert {c for c, _d in found} <= {*PHASES, scopes.REDIST, scopes.OTHER,
                                      scopes.UNSCOPED}
    assert {d for c, d in found if c == scopes.OTHER} <= {
        "tsqr/-", "least_squares/-"}


def test_one_chip_keeps_the_blocked_route():
    """On one chip the blocked route gathers nothing either, and it is
    the one that was timed there: the rule leaves it, whatever the
    aspect."""
    names = op_names(_compiled("1x1"))
    assert not any("el.tsqr" in n for n in names)
    assert any("/el.least_squares/el.qr/" in n for n in names)


_ARRAY = re.compile(r"\b(?:f32|f64)\[([\d,]+)\]")


def test_no_chip_holds_more_of_a_than_its_rows(text22):
    """Every array of the optimized 2x2 program has at most the entries
    of a chip's own share of A (M N / 4 here, B's and the small factors
    well under it): nothing [STAR,STAR] or otherwise replicated with M
    rows, as the blocked route's first panel is."""
    share = M * N // 4
    largest = max(int(np.prod([int(d) for d in dims.split(",")]))
                  for dims in _ARRAY.findall(text22))
    assert largest == share
    # the two collectives of the route besides the hops' all-to-alls: the
    # gather of R factors and of the chips' Y blocks, each p small blocks
    gathers = re.findall(r"= \w+\[([\d,]+)\][^ ]* all-gather(?:-start)?\(",
                         text22)
    assert sorted(gathers) == sorted([f"4,{N},{NRHS}", f"4,{N},{N}"])


def test_blocked_route_would_replicate_the_operand():
    """The parent's program at the same shape, for contrast: the blocked
    route's first panel is the whole operand on every chip."""
    grid = _grid("2x2")
    F, B = _operands(M, N, NRHS, dtype=np.float32)

    def blocked(A, B):
        Ap, tau = el.qr(A)
        return el.apply_q(Ap, tau, B, orient="C").local
    text = jax.jit(blocked).lower(
        _dist(grid, F), _dist(grid, B)).compile().as_text()
    largest = max(int(np.prod([int(d) for d in dims.split(",")]))
                  for dims in _ARRAY.findall(text))
    assert largest >= M * N


def test_precision_reaches_the_products():
    """``precision`` is every matmul's: at HIGH the optimized program
    differs from the default's (HIGHEST), and asking for HIGHEST is the
    default."""
    def dots(text):
        return sorted(re.findall(r"operand_precision=\{(\w+),(\w+)\}", text))
    default = _compiled("2x2")
    highest = _compiled("2x2", precision=jax.lax.Precision.HIGHEST)
    high = _compiled("2x2", precision=jax.lax.Precision.HIGH)
    assert stripped(default) == stripped(highest)
    assert stripped(default) != stripped(high)
    if dots(default):                     # a backend that prints them
        assert {p for pair in dots(default) for p in pair} == {"highest"}
        assert "highest" not in {p for pair in dots(high) for p in pair}


# ------------------------------------------------------------------ tsqr

@pytest.mark.parametrize("grid_name", ["1x1", "2x2", "2x4"])
def test_tsqr_factors_and_names_its_phases(grid_name):
    grid = _grid(grid_name)
    m, k = 1000, 12
    F, _B = _operands(m, k, 1)
    A = el.from_global(F, el.VC, el.STAR, grid=grid)
    jitted = jax.jit(el.tsqr)
    Q, R = jitted(A)
    Qh, Rh = np.asarray(el.to_global(Q)), np.asarray(el.to_global(R))
    assert np.linalg.norm(Qh.T @ Qh - np.eye(k)) < 1e-13
    assert np.linalg.norm(Qh @ Rh - F) < 1e-13 * np.linalg.norm(F)
    assert np.allclose(np.tril(Rh, -1), 0)
    names = op_names(jitted.lower(A).compile().as_text())
    phases = ("local", "applyq") + (("tree",) if grid.size > 1 else ())
    for phase in phases:
        assert any(re.search(rf"/el\.tsqr/(.*/)?k00/{phase}(/|$)", n)
                   for n in names), phase


def test_tsqr_precision_is_the_callers():
    grid = _grid("2x2")
    F, _B = _operands(512, 12, 1, dtype=np.float32)
    A = el.from_global(F, el.VC, el.STAR, grid=grid)
    texts = [stripped(jax.jit(lambda a, p=p: el.tsqr(a, precision=p)).lower(
        A).compile().as_text()) for p in
        (None, jax.lax.Precision.HIGHEST, jax.lax.Precision.HIGH)]
    assert texts[0] == texts[1] != texts[2]


def test_tsqr_refuses_complex_entries():
    """The reflectors take no conjugates: a complex A is an error, not a
    wrong Q and R."""
    grid = _grid("2x2")
    rng = np.random.default_rng(6)
    F = (rng.normal(size=(64, 6)) + 1j * rng.normal(size=(64, 6))).astype(
        np.complex64)
    A = el.from_global(F, el.VC, el.STAR, grid=grid)
    with pytest.raises(ValueError, match="real floating-point"):
        el.tsqr(A)


def test_tsqr_refuses_a_slab_with_fewer_rows_than_columns():
    grid = _grid("2x4")
    F = np.random.default_rng(5).normal(size=(40, 6))       # 5 rows a chip
    A = el.from_global(F, el.VC, el.STAR, grid=grid)
    with pytest.raises(ValueError, match="at least as many rows"):
        el.tsqr(A)
