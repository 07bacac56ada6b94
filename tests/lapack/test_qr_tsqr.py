"""TSQR/CAQR tree panel for QR (ISSUE 6 rider): the tree-reduced panel
must land in the SAME geqrf packing as the classic larfg panel, so every
downstream consumer (apply_q, explicit_q, least_squares) works unchanged.

R's diagonal signs may differ from the classic reduction (the tree fixes
signs so the Householder reconstruction's LU is stable), hence the
comparisons below are |R|-level plus exact self-consistency identities
(orthogonality, A = Q R, apply_q round trip).
"""
import numpy as np
import pytest

from elemental_tpu import MC, MR, from_global, to_global
from elemental_tpu.lapack.qr import qr, apply_q, explicit_q

from ..conftest import compiled


def _dist(g, arr):
    return from_global(arr, MC, MR, grid=g)


def _factor_and_q(A, nb):
    """One program: the blocking ``qr`` records on its factor (``_qr_nb``,
    which ``explicit_q`` reads) does not cross a ``jit`` boundary."""
    Ap, tau = qr(A, nb=nb, panel="tsqr")
    return Ap, explicit_q(Ap, tau)


@pytest.mark.parametrize("shape", [(24, 16), (32, 32), (19, 13), (30, 18)])
def test_tsqr_residual_orthogonality(grid24, shape):
    m, n = shape
    rng = np.random.default_rng(71)
    F = rng.normal(size=(m, n))
    Ap, Q = compiled(_factor_and_q, nb=8)(_dist(grid24, F))
    Q = np.asarray(to_global(Q))
    k = min(m, n)
    R = np.triu(np.asarray(to_global(Ap)))[:k, :]
    assert np.linalg.norm(Q.T @ Q - np.eye(m)) < 1e-12
    assert np.linalg.norm(Q[:, :k] @ R - F) < 1e-12 * np.linalg.norm(F)


def test_tsqr_R_matches_numpy_abs(grid42):
    rng = np.random.default_rng(72)
    F = rng.normal(size=(28, 12))
    Ap, _ = compiled(qr, nb=4, panel="tsqr")(_dist(grid42, F))
    R = np.triu(np.asarray(to_global(Ap)))[:12, :]
    np.testing.assert_allclose(np.abs(R), np.abs(np.linalg.qr(F, mode="r")),
                               atol=1e-11)


def test_tsqr_complex(grid24):
    rng = np.random.default_rng(73)
    F = rng.normal(size=(20, 12)) + 1j * rng.normal(size=(20, 12))
    Ap, Q = compiled(_factor_and_q, nb=4)(_dist(grid24, F))
    Q = np.asarray(to_global(Q))
    R = np.triu(np.asarray(to_global(Ap)))[:12, :]
    assert np.linalg.norm(Q.conj().T @ Q - np.eye(20)) < 1e-11
    assert np.linalg.norm(Q[:, :12] @ R - F) < 1e-11 * np.linalg.norm(F)


def test_tsqr_apply_q_roundtrip_records_nb(grid24):
    """Q (Q^H B) == B through the packed tree factor, using the recorded
    ``_qr_nb`` default blocking (the reused tuner plumbing)."""
    rng = np.random.default_rng(74)
    F = rng.normal(size=(24, 16))
    Ap, tau = qr(_dist(grid24, F), nb=8, panel="tsqr")
    assert getattr(Ap, "_qr_nb", None) == 8
    B = rng.normal(size=(24, 3))
    Bd = _dist(grid24, B)
    out = apply_q(Ap, tau, apply_q(Ap, tau, Bd, orient="C"))
    np.testing.assert_allclose(np.asarray(to_global(out)), B, atol=1e-12)


def test_tsqr_rejects_unknown_panel(grid24):
    rng = np.random.default_rng(75)
    F = rng.normal(size=(16, 8))
    with pytest.raises(ValueError, match="panel"):
        qr(_dist(grid24, F), nb=8, panel="caqr2")


def test_tsqr_least_squares_path(grid24):
    """A tsqr factor drives the same triangular solve as classic: solve a
    tall LS problem both ways and compare the minimizers."""
    rng = np.random.default_rng(76)
    F = rng.normal(size=(30, 10))
    B = rng.normal(size=(30, 2))
    X_np, *_ = np.linalg.lstsq(F, B, rcond=None)
    from elemental_tpu.redist.interior import interior_view
    from elemental_tpu.blas.level1 import make_trapezoidal
    from elemental_tpu.blas.level3 import trsm

    def minimizer(A, B):
        Ap, tau = qr(A, nb=4, panel="tsqr")
        Y = apply_q(Ap, tau, B, orient="C")
        R = make_trapezoidal(interior_view(Ap, (0, 10), (0, 10)), "U")
        return trsm("L", "U", "N", R, interior_view(Y, (0, 10), (0, 2)), nb=4)

    X = compiled(minimizer)(_dist(grid24, F), _dist(grid24, B))
    np.testing.assert_allclose(np.asarray(to_global(X)), X_np, atol=1e-10)
