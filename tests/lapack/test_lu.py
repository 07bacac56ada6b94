"""LU with partial pivoting: residual + pivot-correctness oracles.

Mirrors ``tests/lapack_like/LU.cpp``: ||P A - L U|| / ||A||, solve
residuals, agreement of pivot choices with LAPACK on deterministic cases.

Every case here is about a RESULT, so it runs its driver as one compiled
program (``conftest.compiled``; ISSUE 48); the eager walk is run where a
test needs its hooks (``tests/resilience``, ``tests/obs``).
"""
import jax
import numpy as np
import pytest

import elemental_tpu as el
from elemental_tpu import MC, MR, from_global, to_global
from elemental_tpu.lapack.lu import lu, lu_solve, lu_solve_after, permute_rows

from ..conftest import compiled


def _dist(g, arr):
    return from_global(arr, MC, MR, grid=g)


def _unpack(LUh):
    m, n = LUh.shape
    k = min(m, n)
    L = np.tril(LUh[:, :k], -1) + np.eye(m, k)
    U = np.triu(LUh[:k, :])
    return L, U


@pytest.mark.parametrize("shape", [(24, 24), (32, 20), (20, 32), (19, 19),
                                   (19, 32), (32, 19), (18, 30)])
def test_lu_residual(grid24, shape):
    m, n = shape
    rng = np.random.default_rng(11)
    F = rng.normal(size=(m, n))
    LUd, perm = compiled(lu, nb=8)(_dist(grid24, F))
    LUh = np.asarray(to_global(LUd))
    p = np.asarray(perm)
    L, U = _unpack(LUh)
    PA = F[p, :]
    assert np.linalg.norm(PA - L @ U) / np.linalg.norm(F) < 1e-13
    # partial pivoting => |L| <= 1
    assert np.max(np.abs(L)) <= 1 + 1e-14


def test_lu_vs_numpy_pivots(grid42):
    # deterministic matrix with forced pivoting (growth-factor style)
    n = 16
    F = np.eye(n) * 1e-3 + np.tril(-np.ones((n, n)), -1) + np.triu(np.ones((n, n)), 1)
    import scipy.linalg as sla
    P, L, U = sla.lu(F)
    LUd, perm = compiled(lu, nb=8)(_dist(grid42, F))
    LUh = np.asarray(to_global(LUd))
    Ld, Ud = _unpack(LUh)
    p = np.asarray(perm)
    np.testing.assert_allclose(F[p, :], Ld @ Ud, atol=1e-13)
    np.testing.assert_allclose(np.abs(Ud[-1, -1]), np.abs(U[-1, -1]), rtol=1e-10)


def test_lu_solve(grid24):
    n, nrhs = 24, 5
    rng = np.random.default_rng(12)
    F = rng.normal(size=(n, n)) + n * np.eye(n)
    B = rng.normal(size=(n, nrhs))
    X = compiled(lu_solve, nb=8)(_dist(grid24, F), _dist(grid24, B))
    Xh = np.asarray(to_global(X))
    assert np.linalg.norm(F @ Xh - B) / np.linalg.norm(B) < 1e-12


def test_lu_solve_complex_two_grids(two_grids):
    n, nrhs = 13, 3
    rng = np.random.default_rng(13)
    F = rng.normal(size=(n, n)) + 1j * rng.normal(size=(n, n)) + 2 * n * np.eye(n)
    B = rng.normal(size=(n, nrhs)) + 1j * rng.normal(size=(n, nrhs))
    X = compiled(lu_solve, nb=4)(_dist(two_grids, F), _dist(two_grids, B))
    assert np.linalg.norm(F @ np.asarray(to_global(X)) - B) < 1e-11 * np.linalg.norm(B)


def test_lu_solve_after_reuse(grid24):
    n = 20
    rng = np.random.default_rng(14)
    F = rng.normal(size=(n, n)) + n * np.eye(n)
    LUd, perm = compiled(lu, nb=8)(_dist(grid24, F))
    solve_after = compiled(lu_solve_after, nb=8)
    for seed in (1, 2):
        B = np.random.default_rng(seed).normal(size=(n, 2))
        X = solve_after(LUd, perm, _dist(grid24, B))
        assert np.linalg.norm(F @ np.asarray(to_global(X)) - B) < 1e-12 * np.linalg.norm(B)


def test_permute_rows_roundtrip(grid42):
    m, n = 18, 7
    rng = np.random.default_rng(15)
    F = rng.normal(size=(m, n))
    p = rng.permutation(m)
    import jax.numpy as jnp
    Bp = permute_rows(_dist(grid42, F), jnp.asarray(p))
    np.testing.assert_allclose(np.asarray(to_global(Bp)), F[p, :], rtol=1e-14)
    back = permute_rows(Bp, jnp.asarray(p), inverse=True)
    np.testing.assert_allclose(np.asarray(to_global(back)), F, rtol=1e-14)


@pytest.mark.parametrize("shape", [(24, 24), (32, 20), (20, 32), (19, 19),
                                   (18, 30)])
def test_lu_lookahead_matches_classic(grid24, shape):
    """The pipelined schedule reorders ops but computes the same update
    matmuls element-for-element: factors and pivots must agree with the
    classic right-looking driver to roundoff (crossover disabled so both
    run the full distributed loop)."""
    m, n = shape
    rng = np.random.default_rng(21)
    F = rng.normal(size=(m, n))
    A = _dist(grid24, F)
    LUa, pa = compiled(lu, nb=8, lookahead=True, crossover=0)(A)
    LUb, pb = compiled(lu, nb=8, lookahead=False)(A)
    np.testing.assert_array_equal(np.asarray(pa), np.asarray(pb))
    np.testing.assert_allclose(np.asarray(to_global(LUa)),
                               np.asarray(to_global(LUb)),
                               rtol=1e-12, atol=1e-12)


@pytest.mark.parametrize("shape", [
    pytest.param((48, 48), marks=pytest.mark.slow),
    pytest.param((40, 40), marks=pytest.mark.slow),
    (48, 32), (32, 48)])
def test_lu_crossover_boundary(grid24, shape):
    """Tail crossover-to-local at thresholds just below / at / above the
    remaining-block sizes: pivots match classic exactly and factors to
    roundoff at every threshold (incl. 0 = never and huge = tail on the
    first step)."""
    m, n = shape
    rng = np.random.default_rng(31)
    F = rng.normal(size=(m, n))
    A = _dist(grid24, F)
    LUref, pref = compiled(lu, nb=8, lookahead=False)(A)
    ref = np.asarray(to_global(LUref))
    for xo in [0, 7, 8, 9, 16, 31, 32, 33, 10_000]:
        LU, p = compiled(lu, nb=8, lookahead=True, crossover=xo)(A)
        np.testing.assert_array_equal(np.asarray(p), np.asarray(pref))
        np.testing.assert_allclose(np.asarray(to_global(LU)), ref,
                                   rtol=1e-12, atol=1e-12)
        k = min(m, n)
        got = np.asarray(to_global(LU))
        L = np.tril(got[:, :k], -1) + np.eye(m, k)
        U = np.triu(got[:k, :])
        res = np.linalg.norm(F[np.asarray(p)] - L @ U)
        assert res < 1e-12 * np.linalg.norm(F) * max(m, n)


def test_lu_crossover_classic_opt_in(grid24):
    """Explicit crossover also applies to the classic schedule (mirrors
    cholesky): default classic never crosses over."""
    n = 40
    rng = np.random.default_rng(32)
    F = rng.normal(size=(n, n))
    A = _dist(grid24, F)
    LUa, pa = compiled(lu, nb=8, lookahead=False, crossover=16)(A)
    LUb, pb = compiled(lu, nb=8, lookahead=False)(A)
    np.testing.assert_array_equal(np.asarray(pa), np.asarray(pb))
    np.testing.assert_allclose(np.asarray(to_global(LUa)),
                               np.asarray(to_global(LUb)),
                               rtol=1e-12, atol=1e-12)


def test_lu_lookahead_matches_classic_local():
    """Same agreement on the sequential (1x1 grid) fast path."""
    g1 = el.Grid([jax.devices()[0]])
    rng = np.random.default_rng(22)
    for m, n in [(40, 40), (40, 56), (56, 40), (37, 37)]:
        F = rng.normal(size=(m, n))
        LUa, pa = compiled(lu, nb=16, lookahead=True)(_dist(g1, F))
        LUb, pb = compiled(lu, nb=16, lookahead=False)(_dist(g1, F))
        np.testing.assert_array_equal(np.asarray(pa), np.asarray(pb))
        np.testing.assert_allclose(np.asarray(LUa.local),
                                   np.asarray(LUb.local),
                                   rtol=1e-12, atol=1e-12)
        L, U = _unpack(np.asarray(LUa.local))
        assert np.linalg.norm(F[np.asarray(pa), :n] - (L @ U)[:, :n]) \
            < 1e-12 * np.linalg.norm(F)


def test_lu_update_precision_knob(grid24):
    """update_precision only relaxes the trailing updates: on CPU f64 the
    DEFAULT and HIGHEST paths coincide, so this pins the API and the
    factorization residual, not a bf16 error model."""
    n = 24
    rng = np.random.default_rng(23)
    F = rng.normal(size=(n, n)) + n * np.eye(n)
    LUd, perm = compiled(lu, nb=8, precision=jax.lax.Precision.HIGHEST,
                          update_precision=jax.lax.Precision.DEFAULT)(
        _dist(grid24, F))
    L, U = _unpack(np.asarray(to_global(LUd)))
    p = np.asarray(perm)
    assert np.linalg.norm(F[p, :] - L @ U) / np.linalg.norm(F) < 1e-10


def test_lu_jit(grid24):
    n = 16
    rng = np.random.default_rng(16)
    F = rng.normal(size=(n, n)) + n * np.eye(n)
    A = _dist(grid24, F)
    LUd, perm = jax.jit(lambda a: lu(a, nb=8))(A)
    LUh = np.asarray(to_global(LUd))
    L, U = _unpack(LUh)
    assert np.linalg.norm(F[np.asarray(perm), :] - L @ U) < 1e-12 * np.linalg.norm(F)
