"""Fused Pallas QR panel (ISSUE 17): larfg chain + larft twin contract.

The kernel mirrors ``_panel_qr``'s exact degenerate guards; its
reductions group differently from the XLA (M,)-vector sums, so the
CONTRACT is residual-bounded: the hard assertions here are residuals +
orthonormality, plus agreement with the XLA twin to rounding.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from elemental_tpu.kernels import qr_panel
from elemental_tpu.lapack.qr import _larft, _panel_qr, _panel_v

F32_TOL = 3e-6
F64_TOL = 1e-12


def _recon(pg, tg, m, k):
    Q = np.eye(m)
    for j in range(k):
        v = np.zeros(m)
        v[j] = 1.0
        v[j + 1:] = pg[j + 1:, j]
        Q = Q @ (np.eye(m) - tg[j] * np.outer(v, v))
    return Q, np.triu(pg[:k, :])


@pytest.mark.parametrize("shape", [
    (64, 16), (40, 8), (33, 7),
    # the wide rungs ride the full ladder in `tools/check.sh kernels`
    pytest.param((96, 32), marks=pytest.mark.slow),
    pytest.param((128, 64), marks=pytest.mark.slow)])
@pytest.mark.parametrize("dtype,tol", [(np.float32, F32_TOL),
                                       (np.float64, F64_TOL)])
def test_residual_and_ortho(shape, dtype, tol):
    m, k = shape
    rng = np.random.default_rng(m + k)
    F = rng.normal(size=(m, k)).astype(dtype)
    packed, tau, T = qr_panel(jnp.asarray(F))
    pg, tg = np.asarray(packed), np.asarray(tau)
    Q, R = _recon(pg, tg, m, k)
    assert np.linalg.norm(Q[:, :k] @ R - F) / np.linalg.norm(F) < tol
    assert np.linalg.norm(Q.T @ Q - np.eye(m)) / np.sqrt(m) < tol
    # the fused T must satisfy the larft identity through the same V
    V = np.tril(pg, -1) + np.eye(m, k)
    Texp = np.asarray(_larft(jnp.asarray(V.astype(dtype)),
                             jnp.asarray(tg)))
    np.testing.assert_allclose(np.asarray(T), Texp, rtol=0,
                               atol=(1e-5 if dtype == np.float32 else 1e-12))


@pytest.mark.parametrize("shape", [
    (64, 16), (96, 32),
    pytest.param((128, 64), marks=pytest.mark.slow)])
def test_agrees_with_xla_twin_to_rounding(shape):
    # same larfg conventions (sign of beta, unit v_j, tau) as the XLA
    # twin, so the two agree entry for entry up to summation order
    m, k = shape
    rng = np.random.default_rng(m * k)
    F = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
    packed, tau, T = qr_panel(F)
    packed_x, tau_x = _panel_qr(F)
    T_x = _larft(_panel_v(packed_x), tau_x)
    atol = 4 * k * np.finfo(np.float32).eps * np.sqrt(m)
    np.testing.assert_allclose(np.asarray(packed), np.asarray(packed_x),
                               rtol=0, atol=atol)
    np.testing.assert_allclose(np.asarray(tau), np.asarray(tau_x),
                               rtol=0, atol=atol)
    np.testing.assert_allclose(np.asarray(T), np.asarray(T_x),
                               rtol=0, atol=atol)


def test_graded_columns():
    # columns scaled across 10 orders of magnitude: the larfg guards
    # (degenerate norm, sign-of-alpha) must hold as in the reference
    m, k = 64, 16
    rng = np.random.default_rng(5)
    F = rng.normal(size=(m, k)) * np.logspace(0, -10, k)[None, :]
    F = F.astype(np.float64)
    packed, tau, T = qr_panel(jnp.asarray(F))
    Q, R = _recon(np.asarray(packed), np.asarray(tau), m, k)
    assert np.linalg.norm(Q[:, :k] @ R - F) / np.linalg.norm(F) < F64_TOL


def test_zero_column_degenerate():
    # an exactly-zero column hits the degenerate larfg branch: tau = 0,
    # beta = 0, matching the reference guard; the surrounding columns
    # stay within the residual contract (the lane-padded w-dot groups
    # its reduction differently here, so no bit pin)
    m, k = 32, 8
    rng = np.random.default_rng(6)
    F = rng.normal(size=(m, k)).astype(np.float32)
    F[:, 3] = 0.0
    packed, tau, T = qr_panel(jnp.asarray(F))
    packed_x, tau_x = _panel_qr(jnp.asarray(F))
    assert np.asarray(tau)[3] == np.asarray(tau_x)[3] == 0.0
    np.testing.assert_allclose(np.asarray(packed), np.asarray(packed_x),
                               rtol=0, atol=1e-6)


def test_complex_raises():
    P = jnp.ones((16, 4), jnp.complex64)
    with pytest.raises(ValueError, match="real-only"):
        qr_panel(P)
