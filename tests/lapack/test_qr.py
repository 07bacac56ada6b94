"""QR / ApplyQ / TSQR / LeastSquares oracles.

Mirrors ``tests/lapack_like/QR.cpp``: factorization residual ||A - QR||,
orthogonality ||I - Q^H Q||, solve residuals (SURVEY.md §5).
"""
import numpy as np
import pytest

import elemental_tpu as el
from elemental_tpu import MC, MR, VC, STAR, from_global, to_global
from elemental_tpu.lapack.qr import qr, apply_q, explicit_q, least_squares, tsqr

from ..conftest import compiled


def _dist(g, arr):
    return from_global(arr, MC, MR, grid=g)


@pytest.mark.parametrize("shape", [(24, 24), (32, 16), (16, 32), (19, 13), (13, 19)])
@pytest.mark.parametrize("dtype", [np.float64, np.complex128])
def test_qr_residual_orthogonality(grid24, shape, dtype):
    m, n = shape
    rng = np.random.default_rng(21)
    F = rng.normal(size=(m, n)).astype(dtype)
    if np.issubdtype(dtype, np.complexfloating):
        F = F + 1j * rng.normal(size=(m, n))
    Ap, tau = compiled(qr, nb=8)(_dist(grid24, F))
    Q = np.asarray(to_global(compiled(explicit_q, nb=8)(Ap, tau)))
    k = min(m, n)
    R = np.triu(np.asarray(to_global(Ap)))[:k, :]
    assert np.linalg.norm(np.eye(m) - Q.conj().T @ Q) < 1e-12 * m
    assert np.linalg.norm(F - Q[:, :k] @ R) / np.linalg.norm(F) < 1e-13


def test_qr_vs_numpy_R(grid42):
    m, n = 20, 12
    rng = np.random.default_rng(22)
    F = rng.normal(size=(m, n))
    Ap, tau = compiled(qr, nb=8)(_dist(grid42, F))
    R = np.triu(np.asarray(to_global(Ap)))[:n, :]
    Rnp = np.linalg.qr(F, mode="r")
    np.testing.assert_allclose(np.abs(R), np.abs(Rnp), atol=1e-12)


def test_apply_q_adjoint_roundtrip(grid24):
    m, n, nrhs = 24, 16, 5
    rng = np.random.default_rng(23)
    F = rng.normal(size=(m, n)) + 1j * rng.normal(size=(m, n))
    B = rng.normal(size=(m, nrhs)) + 1j * rng.normal(size=(m, nrhs))
    Ap, tau = compiled(qr, nb=8)(_dist(grid24, F))
    Bd = _dist(grid24, B)
    out = compiled(apply_q, orient="N", nb=8)(
        Ap, tau, compiled(apply_q, orient="C", nb=8)(Ap, tau, Bd))
    np.testing.assert_allclose(np.asarray(to_global(out)), B, atol=1e-12)


@pytest.mark.parametrize("shape", [(32, 8), (40, 12)])
def test_least_squares(grid24, shape):
    m, n = shape
    rng = np.random.default_rng(24)
    F = rng.normal(size=(m, n))
    B = rng.normal(size=(m, 3))
    X = compiled(least_squares, nb=8)(_dist(grid24, F), _dist(grid24, B))
    Xnp, *_ = np.linalg.lstsq(F, B, rcond=None)
    np.testing.assert_allclose(np.asarray(to_global(X)), Xnp, atol=1e-10)


def test_least_squares_complex_two_grids(two_grids):
    m, n = 26, 7
    rng = np.random.default_rng(25)
    F = rng.normal(size=(m, n)) + 1j * rng.normal(size=(m, n))
    B = rng.normal(size=(m, 2)) + 1j * rng.normal(size=(m, 2))
    X = compiled(least_squares, nb=4)(_dist(two_grids, F),
                                      _dist(two_grids, B))
    Xnp, *_ = np.linalg.lstsq(F, B, rcond=None)
    np.testing.assert_allclose(np.asarray(to_global(X)), Xnp, atol=1e-10)


def test_tsqr(grid24):
    m, k = 64, 6
    rng = np.random.default_rng(26)
    F = rng.normal(size=(m, k))
    A = from_global(F, VC, STAR, grid24)
    Q, R = compiled(tsqr)(A)
    Qh = np.asarray(to_global(Q))
    Rh = np.asarray(to_global(R))
    assert np.linalg.norm(Qh.T @ Qh - np.eye(k)) < 1e-13
    np.testing.assert_allclose(Qh @ Rh, F, atol=1e-12)
    assert np.allclose(np.tril(Rh, -1), 0)


def test_qr_jit(grid24):
    import jax
    m, n = 16, 12
    rng = np.random.default_rng(27)
    F = rng.normal(size=(m, n))
    Ap, tau = jax.jit(lambda a: qr(a, nb=8))(_dist(grid24, F))
    R = np.triu(np.asarray(to_global(Ap)))[:n, :]
    Rnp = np.linalg.qr(F, mode="r")
    np.testing.assert_allclose(np.abs(R), np.abs(Rnp), atol=1e-12)


# ---------------------------------------------------------------------
# LQ and column-pivoted QR
# ---------------------------------------------------------------------

def test_lq(grid24):
    import elemental_tpu as el
    rng = np.random.default_rng(30)
    F = rng.normal(size=(8, 20))
    A = el.from_global(F, el.MC, el.MR, grid=grid24)
    Ap, tau = el.lq(A)
    L = np.asarray(el.to_global(el.explicit_l(Ap)))
    I_n = el.from_global(np.eye(20), el.MC, el.MR, grid=grid24)
    Q = np.asarray(el.to_global(el.apply_q_lq(Ap, tau, I_n, orient="N")))
    assert np.linalg.norm(np.triu(L, 1)) == 0
    assert np.linalg.norm(Q.T @ Q - np.eye(20)) < 1e-12
    assert np.linalg.norm(L @ Q[:8] - F) / np.linalg.norm(F) < 1e-13


def _check_cpqr(F, grid, nb):
    import elemental_tpu as el
    from elemental_tpu.lapack.qr import qr_col_piv, apply_q
    m, n = F.shape
    A = el.from_global(F, el.MC, el.MR, grid=grid)
    Ap, tau, jpvt = qr_col_piv(A, nb=nb)
    jp = np.asarray(jpvt)
    kend = min(m, n)
    R = np.triu(np.asarray(el.to_global(Ap))[:kend, :])
    I_m = el.from_global(np.eye(m, dtype=F.dtype), el.MC, el.MR, grid=grid)
    Q = np.asarray(el.to_global(apply_q(Ap, tau, I_m, orient="N", nb=nb)))
    perm = np.concatenate([jp, np.setdiff1d(np.arange(n), jp)]) \
        if n > kend else jp
    rec = Q[:, :kend] @ R
    assert np.linalg.norm(rec - F[:, perm]) / np.linalg.norm(F) < 1e-13
    rd = np.abs(np.diag(R))
    assert np.all(rd[:-1] >= rd[1:] - 1e-10)     # greedy pivot order


def test_qr_col_piv(grid24):
    rng = np.random.default_rng(31)
    _check_cpqr(rng.normal(size=(16, 12)), grid24, nb=4)
    _check_cpqr(rng.normal(size=(12, 12)), grid24, nb=12)
    Fc = rng.normal(size=(12, 8)) + 1j * rng.normal(size=(12, 8))
    _check_cpqr(Fc, grid24, nb=4)


def test_qr_col_piv_rank_revealing(grid24):
    import elemental_tpu as el
    from elemental_tpu.lapack.qr import qr_col_piv
    rng = np.random.default_rng(32)
    F = rng.normal(size=(16, 4)) @ rng.normal(size=(4, 12))   # rank 4
    A = el.from_global(F, el.MC, el.MR, grid=grid24)
    Ap, tau, jpvt = qr_col_piv(A, nb=4)
    R = np.triu(np.asarray(el.to_global(Ap))[:12, :])
    assert abs(R[4, 4]) < 1e-10 * abs(R[0, 0])


# ---------------------------------------------------------------------
# ISSUE 4 satellite: the qr/apply_q blocking footgun is closed
# ---------------------------------------------------------------------

def test_apply_q_defaults_to_factorization_blocking(grid24):
    """qr() records the block size it used; apply_q(nb=None) reuses it
    even when the factorization ran with a NON-default nb (previously a
    silent-wrong-results trap)."""
    m, n, nrhs = 24, 16, 5
    rng = np.random.default_rng(31)
    F = rng.normal(size=(m, n))
    B = rng.normal(size=(m, nrhs))
    Ap, tau = qr(_dist(grid24, F), nb=8)      # non-default blocking
    assert getattr(Ap, "_qr_nb") == 8
    Bd = _dist(grid24, B)
    out = apply_q(Ap, tau, apply_q(Ap, tau, Bd, orient="C"), orient="N")
    np.testing.assert_allclose(np.asarray(to_global(out)), B, atol=1e-12)


def test_apply_q_mismatched_nb_raises(grid24):
    m, n = 24, 16
    rng = np.random.default_rng(32)
    Ap, tau = qr(_dist(grid24, rng.normal(size=(m, n))), nb=8)
    Bd = _dist(grid24, rng.normal(size=(m, 3)))
    with pytest.raises(ValueError, match="block size"):
        apply_q(Ap, tau, Bd, nb=4)
    # a matching explicit nb (same derived blocking) is still accepted
    out = apply_q(Ap, tau, apply_q(Ap, tau, Bd, orient="C", nb=8), nb=8)
    np.testing.assert_allclose(np.asarray(to_global(out)),
                               np.asarray(to_global(Bd)), atol=1e-12)


def test_qr_col_piv_records_blocking(grid24):
    from elemental_tpu.lapack.qr import qr_col_piv
    rng = np.random.default_rng(33)
    Ap, tau, jpvt = qr_col_piv(_dist(grid24, rng.normal(size=(16, 12))),
                               nb=4)
    assert getattr(Ap, "_qr_nb") == 4
    Bd = _dist(grid24, rng.normal(size=(16, 2)))
    with pytest.raises(ValueError, match="block size"):
        apply_q(Ap, tau, Bd, nb=12)
