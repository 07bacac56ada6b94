"""Fused Pallas LU panel (ISSUE 17): the twin contract.

The unblocked fused kernel mirrors ``lapack.lu._panel_lu_unb`` step for
step -- same candidate mask, same first-max tie-breaking -- so the PIVOT
SEQUENCE must be identical, including on constructed |pivot| ties.  The
packed factor is the same arithmetic, but whether two backends (here:
the Pallas interpreter and XLA:CPU; on the chip: Mosaic and XLA:TPU)
contract ``a - l*u`` into one rounding or two is theirs to decide, so
the factor is held to a few ulps of the panel's scale, not to the bit.
The chunked mode reorders the trailing updates, so it is
residual-bounded.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from elemental_tpu.kernels import lu_panel
from elemental_tpu.lapack.lu import _panel_lu, _panel_lu_unb


@pytest.mark.parametrize("shape,nbw", [
    ((64, 16), 16), ((40, 40), 40), ((8, 3), 3), ((33, 7), 7),
    # the wide rungs ride the full ladder in `tools/check.sh kernels`
    pytest.param((96, 32), 32, marks=pytest.mark.slow),
    pytest.param((128, 64), 64, marks=pytest.mark.slow)])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_unblocked_same_pivots_factor_to_rounding(shape, nbw, dtype):
    rng = np.random.default_rng(sum(shape))
    P = jnp.asarray(rng.normal(size=shape).astype(dtype))
    packed_p, perm_p = lu_panel(P, nbw)
    packed_x, perm_x = _panel_lu_unb(P, nbw)
    np.testing.assert_array_equal(np.asarray(perm_p), np.asarray(perm_x))
    np.testing.assert_allclose(
        np.asarray(packed_p), np.asarray(packed_x), rtol=0,
        atol=8 * nbw * np.finfo(dtype).eps * float(jnp.abs(P).max()))


def test_pivot_ties_break_identically():
    # columns engineered so several rows tie on |value| at each pivot
    # search: jnp.argmax takes the FIRST max, and the fused kernel must
    # inherit exactly that choice
    m, w = 32, 8
    P = np.zeros((m, w), dtype=np.float32)
    rng = np.random.default_rng(3)
    for j in range(w):
        P[:, j] = rng.integers(1, 4, size=m).astype(np.float32)
        P[j::5, j] = 3.0                     # repeated maxima
        P[:, j] *= np.sign(rng.normal(size=m)) + 0.5
    P = jnp.asarray(P)
    packed_p, perm_p = lu_panel(P, w)
    packed_x, perm_x = _panel_lu_unb(P, w)
    np.testing.assert_array_equal(np.asarray(perm_p), np.asarray(perm_x))
    np.testing.assert_allclose(np.asarray(packed_p), np.asarray(packed_x),
                               rtol=0, atol=1e-5)


@pytest.mark.parametrize("inner", [8, 16, 32])
def test_chunked_residual_and_pivots(inner):
    m, w = 96, 64
    rng = np.random.default_rng(9)
    F = rng.normal(size=(m, w)).astype(np.float32)
    packed, perm = lu_panel(jnp.asarray(F), w, inner=inner)
    lu_ = np.asarray(packed)
    p = np.asarray(perm)
    L = np.tril(lu_[:, :w], -1) + np.eye(m, w)
    U = np.triu(lu_[:w, :])
    assert np.linalg.norm(F[p] - L @ U) / np.linalg.norm(F) < 1e-5
    # chunked pivoting IS the unblocked pivoting (chunking only reorders
    # the trailing updates, not the per-column search)
    _, perm_ref = _panel_lu(jnp.asarray(F), w, None, (inner,))
    np.testing.assert_array_equal(p, np.asarray(perm_ref))


def test_complex_raises():
    P = jnp.ones((16, 4), jnp.complex64)
    with pytest.raises(ValueError, match="complex"):
        lu_panel(P, 4)
