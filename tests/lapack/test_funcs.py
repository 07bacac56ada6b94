"""Matrix-function oracles (funcs layer).

Reference test style: residual/identity oracles as in Elemental's
``tests/lapack_like`` drivers (``Polar``: ||U^H U - I||, ||A - UH||;
``Sign``: agreement with the eigen-constructed truth; inverses: ||A X - I||).
"""
import numpy as np
import pytest

import elemental_tpu as el
from ..conftest import compiled


def _g(F, grid):
    return el.from_global(F, el.MC, el.MR, grid=grid)


def _t(A):
    return np.asarray(el.to_global(A))


def test_polar_square(grid24):
    rng = np.random.default_rng(0)
    F = rng.normal(size=(24, 24))
    U, H = compiled(el.polar)(_g(F, grid24))
    Ug, Hg = _t(U), _t(H)
    assert np.linalg.norm(Ug.T @ Ug - np.eye(24)) < 1e-13
    assert np.linalg.norm(Ug @ Hg - F) / np.linalg.norm(F) < 1e-14
    assert np.linalg.norm(Hg - Hg.T) < 1e-13
    assert np.min(np.linalg.eigvalsh(Hg)) > -1e-12


@pytest.mark.slow
def test_polar_tall_wide_complex(grid24):
    rng = np.random.default_rng(1)
    F = rng.normal(size=(32, 16))
    U, H = compiled(el.polar)(_g(F, grid24))
    Ug, Hg = _t(U), _t(H)
    assert np.linalg.norm(Ug.T @ Ug - np.eye(16)) < 1e-13
    assert np.linalg.norm(Ug @ Hg - F) / np.linalg.norm(F) < 1e-14
    W = rng.normal(size=(16, 32))
    U2, H2 = compiled(el.polar)(_g(W, grid24))
    U2g, H2g = _t(U2), _t(H2)
    assert np.linalg.norm(U2g @ U2g.T - np.eye(16)) < 1e-13
    assert np.linalg.norm(U2g @ H2g - W) / np.linalg.norm(W) < 1e-13
    C = rng.normal(size=(24, 24)) + 1j * rng.normal(size=(24, 24))
    U3, H3 = compiled(el.polar)(_g(C, grid24))
    U3g, H3g = _t(U3), _t(H3)
    assert np.linalg.norm(U3g.conj().T @ U3g - np.eye(24)) < 1e-13
    assert np.linalg.norm(U3g @ H3g - C) / np.linalg.norm(C) < 1e-14


def test_polar_ill_conditioned(grid24):
    rng = np.random.default_rng(2)
    Q1, _ = np.linalg.qr(rng.normal(size=(24, 24)))
    Q2, _ = np.linalg.qr(rng.normal(size=(24, 24)))
    s = np.logspace(0, -10, 24)          # cond 1e10
    F = (Q1 * s) @ Q2.T
    U, H = compiled(el.polar)(_g(F, grid24))
    Ug, Hg = _t(U), _t(H)
    assert np.linalg.norm(Ug.T @ Ug - np.eye(24)) < 1e-10
    assert np.linalg.norm(Ug @ Hg - F) / np.linalg.norm(F) < 1e-12


def test_sign(grid24):
    rng = np.random.default_rng(3)
    V = rng.normal(size=(16, 16)) + 3 * np.eye(16)
    d = np.concatenate([rng.uniform(0.5, 2, 8), -rng.uniform(0.5, 2, 8)])
    A = V @ np.diag(d) @ np.linalg.inv(V)
    S_true = V @ np.diag(np.sign(d)) @ np.linalg.inv(V)
    Sg = _t(el.sign(_g(A, grid24)))
    assert np.linalg.norm(Sg - S_true) / np.linalg.norm(S_true) < 1e-10
    assert np.linalg.norm(Sg @ Sg - np.eye(16)) < 1e-10


def test_inverse(grid24):
    rng = np.random.default_rng(4)
    F = rng.normal(size=(24, 24)) + 6 * np.eye(24)
    X = _t(el.inverse(_g(F, grid24)))
    assert np.linalg.norm(F @ X - np.eye(24)) < 1e-12


def test_triangular_inverse(grid24):
    rng = np.random.default_rng(5)
    L = np.tril(rng.normal(size=(24, 24))) + 4 * np.eye(24)
    X = _t(el.triangular_inverse("L", _g(L, grid24)))
    assert np.linalg.norm(np.tril(X) @ L - np.eye(24)) < 1e-12
    U = np.triu(rng.normal(size=(24, 24))) + 4 * np.eye(24)
    Xu = _t(el.triangular_inverse("U", _g(U, grid24)))
    assert np.linalg.norm(np.triu(Xu) @ U - np.eye(24)) < 1e-12


def test_hpd_inverse(grid24):
    rng = np.random.default_rng(6)
    G = rng.normal(size=(24, 24))
    F = G @ G.T / 24 + 2 * np.eye(24)
    X = _t(el.hpd_inverse(_g(F, grid24)))
    assert np.linalg.norm(F @ X - np.eye(24)) < 1e-12


def test_pseudoinverse(grid24):
    rng = np.random.default_rng(7)
    F = rng.normal(size=(32, 16))                 # tall full rank
    P = _t(el.pseudoinverse(_g(F, grid24)))
    assert np.linalg.norm(P @ F - np.eye(16)) < 1e-10
    # rank deficient: A pinv(A) A == A
    B = rng.normal(size=(24, 8)) @ rng.normal(size=(8, 24))
    Pb = _t(el.pseudoinverse(_g(B, grid24)))
    assert np.linalg.norm(B @ Pb @ B - B) / np.linalg.norm(B) < 1e-10


def test_square_root(grid24):
    rng = np.random.default_rng(8)
    G = rng.normal(size=(24, 24))
    F = G @ G.T / 24 + 2 * np.eye(24)
    Y = _t(el.square_root(_g(F, grid24)))
    assert np.linalg.norm(Y @ Y - F) / np.linalg.norm(F) < 1e-11
    Y2 = _t(el.hpd_square_root(_g(F, grid24)))
    assert np.linalg.norm(Y2 @ Y2 - F) / np.linalg.norm(F) < 1e-11
    assert np.linalg.norm(Y2 - Y2.T) < 1e-11
