"""Factorization-variant cross-checks (VERDICT r4 item 7).

Reference oracle style (SURVEY.md §5): agreement between independent
algorithm variants (``tests/blas_like/Gemm.cpp`` runs every SUMMA variant
against each other) and residual identities per factorization.
"""
import numpy as np
import pytest

import elemental_tpu as el

from ..conftest import compiled


def _g(F, grid):
    return el.from_global(np.asarray(F, np.float64), el.MC, el.MR, grid=grid)


def _t(A):
    return np.asarray(el.to_global(A))


# ---------------------------------------------------------------------
# SUMMA-Dot
# ---------------------------------------------------------------------

def test_gemm_dot_vs_variants(two_grids):
    """Small C, long inner dim: the SUMMA-Dot case, cross-checked against
    every other schedule."""
    rng = np.random.default_rng(0)
    m, k, n = 6, 300, 5
    Fa = rng.normal(size=(m, k))
    Fb = rng.normal(size=(k, n))
    ref = Fa @ Fb
    A, B = _g(Fa, two_grids), _g(Fb, two_grids)
    for alg in ("dot", "A", "B", "C", "auto", "gspmd"):
        C = el.gemm(A, B, alg=alg)
        assert np.allclose(_t(C), ref, atol=1e-10), alg


def test_gemm_dot_accumulates(two_grids):
    rng = np.random.default_rng(1)
    Fa = rng.normal(size=(4, 120))
    Fb = rng.normal(size=(120, 3))
    Fc = rng.normal(size=(4, 3))
    C = el.gemm(_g(Fa, two_grids), _g(Fb, two_grids), alpha=2.0, beta=-1.0,
                C=_g(Fc, two_grids), alg="dot")
    assert np.allclose(_t(C), 2 * Fa @ Fb - Fc, atol=1e-10)


# ---------------------------------------------------------------------
# QuasiTrsm
# ---------------------------------------------------------------------

def _quasi_upper(rng, n, nblocks2x2):
    """Random well-conditioned upper quasi-triangular (real Schur-like)."""
    T = np.triu(rng.normal(size=(n, n))) + 3 * np.eye(n)
    pos = rng.choice(n - 1, nblocks2x2, replace=False)
    pos = [p for p in sorted(pos) if p == 0 or (p - 1 not in pos)]
    for p in pos:
        # complex-pair 2x2 block [a b; -b a]
        a, b = T[p, p], 1.0 + abs(rng.normal())
        T[p + 1, p + 1] = a
        T[p, p + 1] = b
        T[p + 1, p] = -b
    return T


@pytest.mark.parametrize("side,orient", [("L", "N"), ("L", "T"),
                                         ("R", "N"), ("R", "T")])
def test_quasi_trsm(two_grids, side, orient):
    rng = np.random.default_rng(2)
    n, k = 37, 5
    T = _quasi_upper(rng, n, 6)
    B = rng.normal(size=(n, k) if side == "L" else (k, n))
    X = el.quasi_trsm(side, orient, _g(T, two_grids), _g(B, two_grids),
                      nb=8)
    opT = T.T if orient == "T" else T
    ref = np.linalg.solve(opT, B) if side == "L" \
        else (B @ np.linalg.inv(opT))
    assert np.allclose(_t(X), ref, atol=1e-9)


def _quasi_upper_complex(rng, n, nblocks2x2):
    """Random well-conditioned COMPLEX upper quasi-triangular matrix."""
    T = np.triu(rng.normal(size=(n, n)) + 1j * rng.normal(size=(n, n))) \
        + 4 * np.eye(n)
    pos = rng.choice(n - 1, nblocks2x2, replace=False)
    pos = [p for p in sorted(pos) if p == 0 or (p - 1 not in pos)]
    for p in pos:
        a, b = T[p, p], (1.0 + abs(rng.normal())) * (1 + 0.5j)
        T[p + 1, p + 1] = a
        T[p, p + 1] = b
        T[p + 1, p] = -np.conj(b)
    return T


@pytest.mark.parametrize("side,orient", [("L", "C"), ("R", "C"),
                                         ("L", "N"), ("R", "T")])
def test_quasi_trsm_complex_conj(two_grids, side, orient):
    """quasi_trsm with complex operands, exercising the conj branches of
    the panel solve and off-panel update (orient 'C': op(T) = T^H), vs
    numpy.linalg.solve on the conjugate-transposed system."""
    rng = np.random.default_rng(9)
    n, k = 37, 5
    T = _quasi_upper_complex(rng, n, 6)
    B = rng.normal(size=(n, k) if side == "L" else (k, n)) \
        + 1j * rng.normal(size=(n, k) if side == "L" else (k, n))
    def _gc(F):          # complex-preserving (module _g casts to float64)
        return el.from_global(np.asarray(F, np.complex128), el.MC, el.MR,
                              grid=two_grids)

    X = el.quasi_trsm(side, orient, _gc(T), _gc(B), nb=8)
    opT = {"N": T, "T": T.T, "C": np.conj(T).T}[orient]
    ref = np.linalg.solve(opT, B) if side == "L" \
        else (B @ np.linalg.inv(opT))
    assert np.allclose(_t(X), ref, atol=1e-9)


def test_quasi_trsm_matches_trsm_on_triangular(two_grids):
    """With zero subdiagonal, quasi_trsm must agree with plain trsm."""
    rng = np.random.default_rng(3)
    n, k = 24, 4
    T = np.triu(rng.normal(size=(n, n))) + 3 * np.eye(n)
    B = rng.normal(size=(n, k))
    X1 = el.quasi_trsm("L", "N", _g(T, two_grids), _g(B, two_grids), nb=8)
    X2 = el.trsm("L", "U", "N", _g(T, two_grids), _g(B, two_grids), nb=8)
    assert np.allclose(_t(X1), _t(X2), atol=1e-10)


# ---------------------------------------------------------------------
# pivoted Cholesky
# ---------------------------------------------------------------------

def test_cholesky_pivoted_hpd(two_grids):
    rng = np.random.default_rng(4)
    n = 30
    G = rng.normal(size=(n, n))
    F = G @ G.T + n * np.eye(n)
    L, perm, rank = el.cholesky_pivoted(_g(F, two_grids))
    Lg = _t(L)
    p = np.asarray(perm)
    assert int(rank) == n
    assert np.allclose(Lg @ Lg.T, F[np.ix_(p, p)], atol=1e-9)
    assert np.allclose(Lg, np.tril(Lg))
    # pivoted diag is non-increasing (the full-pivot invariant)
    d = np.diag(Lg)
    assert np.all(d[:-1] >= d[1:] - 1e-12)
    # cross-check against the unpivoted variant through the permutation
    L0 = _t(el.cholesky(_g(F[np.ix_(p, p)], two_grids)))
    assert np.allclose(Lg, L0, atol=1e-8)


def test_cholesky_pivoted_rank_deficient(two_grids):
    rng = np.random.default_rng(5)
    n, rk = 24, 9
    G = rng.normal(size=(n, rk))
    F = G @ G.T                     # PSD, rank rk
    L, perm, rank = el.cholesky_pivoted(_g(F, two_grids), tol=1e-10)
    Lg = _t(L)
    p = np.asarray(perm)
    assert int(rank) == rk
    assert np.allclose(Lg @ Lg.T, F[np.ix_(p, p)], atol=1e-8)


# ---------------------------------------------------------------------
# LU with complete pivoting
# ---------------------------------------------------------------------

def test_lu_full_pivot(two_grids):
    rng = np.random.default_rng(6)
    m = 29
    F = rng.normal(size=(m, m))
    LU, rp, cp = el.lu_full_pivot(_g(F, two_grids))
    lug = _t(LU)
    L = np.tril(lug, -1) + np.eye(m)
    U = np.triu(lug)
    rp, cp = np.asarray(rp), np.asarray(cp)
    assert np.allclose(L @ U, F[np.ix_(rp, cp)], atol=1e-9)
    # complete pivoting controls growth: |L| <= 1 everywhere
    assert np.abs(L).max() <= 1 + 1e-12
    # cross-check vs partial pivoting: both must reconstruct F through
    # their permutations
    LU2, perm2 = el.lu(_g(F[:, cp], two_grids))
    L2 = np.tril(_t(LU2), -1) + np.eye(m)
    U2 = np.triu(_t(LU2))
    assert np.allclose(L2 @ U2, F[np.ix_(np.asarray(perm2), cp)],
                       atol=1e-9)


def test_lu_full_pivot_growth_matrix(two_grids):
    """gepp_growth defeats partial pivoting's growth bound; complete
    pivoting keeps |U| bounded (the classic Wilkinson example)."""
    n = 16
    F = np.eye(n) - np.tril(np.ones((n, n)), -1)
    F[:, -1] = 1.0
    LU, rp, cp = el.lu_full_pivot(_g(F, two_grids))
    U = np.triu(_t(LU))
    assert np.abs(U).max() < 8          # partial pivoting gives 2^(n-1)
    L = np.tril(_t(LU), -1) + np.eye(n)
    assert np.allclose(L @ U, F[np.ix_(np.asarray(rp), np.asarray(cp))],
                       atol=1e-10)


# ---------------------------------------------------------------------
# RQ
# ---------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(12, 20), (15, 15), (20, 12)])
def test_rq(two_grids, shape):
    rng = np.random.default_rng(7)
    m, n = shape
    F = rng.normal(size=(m, n))
    R, Q = compiled(el.rq)(_g(F, two_grids))
    Rg, Qg = _t(R), _t(Q)
    k = min(m, n)
    assert Rg.shape == (m, k) and Qg.shape == (k, n)
    assert np.allclose(Qg @ Qg.T, np.eye(k), atol=1e-9)
    assert np.allclose(Rg @ Qg, F, atol=1e-9)
    # R is upper-triangular against the bottom-right corner
    if m <= n:
        assert np.allclose(Rg, np.triu(Rg), atol=1e-10)
    else:
        assert np.allclose(Rg[m - k:], np.triu(Rg[m - k:]), atol=1e-10)


def test_quasi_trsm_bump_at_panel_boundary(two_grids):
    """A 2x2 block straddling a panel split must extend the panel by a
    whole distribution grain (view offsets are stride-multiples)."""
    rng = np.random.default_rng(8)
    n, k = 16, 3
    T = np.triu(rng.normal(size=(n, n))) + 3 * np.eye(n)
    T[8, 7] = -1.5                     # bump exactly at the nb=8 split
    T[8, 8] = T[7, 7]
    T[7, 8] = 1.5
    B = rng.normal(size=(n, k))
    X = el.quasi_trsm("L", "N", _g(T, two_grids), _g(B, two_grids), nb=8)
    assert np.allclose(_t(X), np.linalg.solve(T, B), atol=1e-9)


def test_cholesky_pivoted_scaled_identity(two_grids):
    """Rank threshold anchors on A's original diagonal scale: a tiny but
    perfectly conditioned matrix is full rank (pstrf semantics)."""
    n = 8
    F = 1e-20 * np.eye(n)
    L, perm, rank = el.cholesky_pivoted(_g(F, two_grids), tol=1e-6)
    assert int(rank) == n
    Lg = _t(L)
    p = np.asarray(perm)
    assert np.allclose(Lg @ Lg.T, F[np.ix_(p, p)], rtol=1e-10)


def test_cholesky_mod_up_and_downdate(two_grids):
    """Rank-k update then the inverse downdate returns the original
    factor (El::CholeskyMod oracle)."""
    rng = np.random.default_rng(9)
    n, k = 22, 3
    G0 = rng.normal(size=(n, n))
    F = G0 @ G0.T + n * np.eye(n)
    V = rng.normal(size=(n, k))
    L = el.cholesky(_g(F, two_grids))
    L2 = el.cholesky_mod(L, _g(V, two_grids), 1.5)
    L2g = _t(L2)
    assert np.allclose(L2g @ L2g.T, F + 1.5 * V @ V.T, atol=1e-9)
    L3 = el.cholesky_mod(L2, _g(V, two_grids), -1.5)
    L3g = _t(L3)
    assert np.allclose(L3g @ L3g.T, F, atol=1e-8)
    assert np.allclose(L3g, _t(L), atol=1e-8)


def test_cholesky_mod_indefinite_downdate_raises(two_grids):
    L = el.cholesky(_g(np.eye(6), two_grids))
    V = np.zeros((6, 1)); V[0] = 2.0
    with pytest.raises(ValueError):
        el.cholesky_mod(L, _g(V, two_grids), -1.0)
