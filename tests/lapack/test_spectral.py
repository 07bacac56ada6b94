"""Spectral-layer oracles.

Reference test style (SURVEY.md §5): ``tests/lapack_like/HermitianEig.cpp``
residuals ||A Z - Z diag(w)||/||A||, orthogonality ||I - Z^H Z||, subset
consistency; SVD drivers check singular values against the sequential
oracle and the reconstruction residual.
"""
import numpy as np
import pytest

import elemental_tpu as el
from elemental_tpu.lapack.funcs import _qdwh_eig
from ..conftest import compiled


def _g(F, grid):
    return el.from_global(F, el.MC, el.MR, grid=grid)


def _t(A):
    return np.asarray(el.to_global(A))


def _sym(n, seed=0, cplx=False):
    rng = np.random.default_rng(seed)
    if cplx:
        G = rng.normal(size=(n, n)) + 1j * rng.normal(size=(n, n))
        return (G + G.conj().T) / 2
    G = rng.normal(size=(n, n))
    return (G + G.T) / 2


def _check_eig(F, w, Z, tol=1e-12):
    n = F.shape[0]
    wn = np.linalg.eigvalsh(F)
    assert np.linalg.norm(np.asarray(w) - wn) / max(np.linalg.norm(wn), 1) < tol
    Zg = _t(Z)
    assert np.linalg.norm(F @ Zg - Zg @ np.diag(np.asarray(w))) \
        / np.linalg.norm(F) < tol
    assert np.linalg.norm(Zg.conj().T @ Zg - np.eye(n)) < tol * n


def test_herm_eig_real(grid24):
    F = _sym(24, 0)
    w, Z = el.herm_eig(_g(F, grid24))
    _check_eig(F, w, Z)


def test_herm_eig_complex(grid24):
    F = _sym(24, 1, cplx=True)
    w, Z = el.herm_eig(_g(F, grid24))
    _check_eig(F, w, Z)


def test_herm_eig_one_triangle(grid24):
    """Only the selected triangle may be read (poison the other)."""
    F = _sym(24, 2)
    P = F.copy()
    P[np.triu_indices(24, 1)] = np.nan
    w, Z = el.herm_eig(_g(P, grid24), uplo="L")
    _check_eig(F, w, Z)


def test_herm_eig_subset_index(grid24):
    F = _sym(24, 3)
    wn = np.linalg.eigvalsh(F)
    w, Z = el.herm_eig(_g(F, grid24), subset=("index", 2, 6))
    assert np.allclose(np.asarray(w), wn[2:7], atol=1e-12)
    Zg = _t(Z)
    assert Zg.shape == (24, 5)
    assert np.linalg.norm(F @ Zg - Zg @ np.diag(np.asarray(w))) < 1e-11


def test_herm_eig_subset_value_half_open(grid24):
    """range='V' selects (lo, hi]: lo itself excluded, hi included."""
    d = np.arange(1.0, 25.0)
    F = np.diag(d)
    w = el.herm_eig(_g(F, grid24), vectors=False, subset=("value", 5.0, 9.0))
    assert np.allclose(np.sort(np.asarray(w)), [6.0, 7.0, 8.0, 9.0])


def test_skew_herm_eig_subset(grid24):
    """ADVICE repro: subset=('index',0,3) must return the 4 SMALLEST
    imaginary parts, not the largest."""
    rng = np.random.default_rng(4)
    G = rng.normal(size=(16, 16))
    F = G - G.T                                   # skew-symmetric
    imag_all = np.sort(np.linalg.eigvals(F).imag)
    w, Z = el.skew_herm_eig(_g(F, grid24), subset=("index", 0, 3))
    assert np.allclose(np.asarray(w), imag_all[:4], atol=1e-11)
    Zg = _t(Z)
    # residual: A z = (i w) z
    r = F.astype(complex) @ Zg - Zg @ np.diag(1j * np.asarray(w))
    assert np.linalg.norm(r) / max(np.linalg.norm(F), 1) < 1e-11
    # value window on the imaginary parts: (lo, hi].  Its ends stand 1e-9
    # above two eigenvalues (gaps are 0.33 and more), not ON them: whether a
    # computed eigenvalue falls on this or that side of its float64 twin
    # from numpy is the last bit's business (ISSUE 38 moved one)
    lo, hi = imag_all[5] + 1e-9, imag_all[9] + 1e-9
    wv = el.skew_herm_eig(_g(F, grid24), vectors=False,
                          subset=("value", lo, hi))
    assert np.allclose(np.asarray(wv), imag_all[6:10], atol=1e-11)


def test_herm_gen_def_eig(grid24):
    rng = np.random.default_rng(5)
    A = _sym(16, 6)
    G = rng.normal(size=(16, 16))
    B = G @ G.T / 16 + 2 * np.eye(16)
    w, X = el.herm_gen_def_eig(_g(A, grid24), _g(B, grid24))
    Xg = _t(X)
    r = A @ Xg - B @ Xg @ np.diag(np.asarray(w))
    assert np.linalg.norm(r) / np.linalg.norm(A) < 1e-11
    assert np.linalg.norm(Xg.T @ B @ Xg - np.eye(16)) < 1e-10


def test_hermitian_svd(grid24):
    F = _sym(24, 7)
    U, s, V = el.hermitian_svd(_g(F, grid24))
    sn = np.linalg.svd(F, compute_uv=False)
    assert np.allclose(np.asarray(s), sn, atol=1e-12)
    Ug, Vg = _t(U), _t(V)
    rec = Ug @ np.diag(np.asarray(s)) @ Vg.T
    assert np.linalg.norm(rec - F) / np.linalg.norm(F) < 1e-12


def _check_svd(F, U, s, V, tol=1e-12):
    sn = np.linalg.svd(F, compute_uv=False)
    k = len(np.asarray(s))
    assert np.allclose(np.asarray(s), sn[:k], atol=tol * max(sn[0], 1))
    Ug, Vg = _t(U), _t(V)
    rec = Ug @ np.diag(np.asarray(s)) @ Vg.conj().T
    assert np.linalg.norm(rec - F) / np.linalg.norm(F) < tol
    assert np.linalg.norm(Ug.conj().T @ Ug - np.eye(k)) < tol * k
    assert np.linalg.norm(Vg.conj().T @ Vg - np.eye(k)) < tol * k


def test_svd_square(grid24):
    """Round-2 regression: svd() on square input crashed (missing funcs)."""
    rng = np.random.default_rng(8)
    F = rng.normal(size=(24, 24))
    U, s, V = compiled(el.svd)(_g(F, grid24))
    _check_svd(F, U, s, V)


@pytest.mark.slow
def test_svd_square_complex(grid24):
    rng = np.random.default_rng(9)
    F = rng.normal(size=(16, 16)) + 1j * rng.normal(size=(16, 16))
    U, s, V = compiled(el.svd)(_g(F, grid24))
    _check_svd(F, U, s, V)


def test_svd_tall_chan(grid24):
    rng = np.random.default_rng(10)
    F = rng.normal(size=(48, 16))
    U, s, V = compiled(el.svd, approach="chan")(_g(F, grid24))
    _check_svd(F, U, s, V)


def test_svd_wide(grid24):
    rng = np.random.default_rng(11)
    F = rng.normal(size=(16, 40))
    U, s, V = compiled(el.svd)(_g(F, grid24))
    _check_svd(F, U, s, V)


def test_svd_values_only(grid24):
    rng = np.random.default_rng(12)
    F = rng.normal(size=(24, 24))
    s = compiled(el.svd, vectors=False)(_g(F, grid24))
    assert np.allclose(np.asarray(s), np.linalg.svd(F, compute_uv=False),
                       atol=1e-12)


# ---------------------------------------------------------------------
# QDWH-eig: the scalable (PMRRR-replacement) path
# ---------------------------------------------------------------------

@pytest.mark.slow
def test_qdwh_eig_recursive(grid24):
    """Small base forces >= 2 levels of spectral divide-and-conquer."""
    F = _sym(48, 13)
    A = _g(F, grid24)
    w, Z = _qdwh_eig(A, "L", True, base=12)
    _check_eig(F, w, Z, tol=1e-12)
    # subset rides the same path
    wn = np.linalg.eigvalsh(F)
    ws = _qdwh_eig(A, "L", False, subset=("index", 3, 9), base=12)
    assert np.allclose(np.asarray(ws), wn[3:10], atol=1e-12)


def test_qdwh_eig_public_api(grid24):
    F = _sym(24, 14)
    w, Z = el.herm_eig(_g(F, grid24), approach="qdwh")
    _check_eig(F, w, Z)


@pytest.mark.slow
def test_qdwh_eig_clustered(grid24):
    """Near-multiple-of-identity blocks must deflate, not loop."""
    rng = np.random.default_rng(15)
    Q, _ = np.linalg.qr(rng.normal(size=(32, 32)))
    d = np.concatenate([np.full(16, 2.0), np.full(16, 5.0)])
    F = (Q * d) @ Q.T
    F = (F + F.T) / 2
    w, Z = _qdwh_eig(_g(F, grid24), "L", True, base=8)
    assert np.allclose(np.sort(np.asarray(w)), np.sort(d), atol=1e-10)
    Zg = _t(Z)
    assert np.linalg.norm(F @ Zg - Zg @ np.diag(np.asarray(w))) \
        / np.linalg.norm(F) < 1e-10
