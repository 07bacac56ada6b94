"""Props oracles: determinant/condition/inertia/norm estimates."""
import functools

import jax
import numpy as np
import pytest

import elemental_tpu as el


def _dm(F, grid):
    return el.from_global(F, el.MC, el.MR, grid=grid)


def test_determinant(grid24):
    rng = np.random.default_rng(0)
    A = rng.normal(size=(12, 12))
    det = complex(np.asarray(el.determinant(_dm(A, grid24))))
    ref = np.linalg.det(A)
    assert abs(det - ref) / abs(ref) < 1e-12


def test_safe_determinant(grid24):
    rng = np.random.default_rng(1)
    A = rng.normal(size=(10, 10)) * 1e3       # would overflow naive prod^n
    rho, kappa, n = el.safe_determinant(_dm(A, grid24))
    sign_ref, logabs_ref = np.linalg.slogdet(A)
    assert abs(complex(np.asarray(rho)) - sign_ref) < 1e-10
    assert abs(float(np.asarray(kappa)) * n - logabs_ref) < 1e-8


def test_hpd_determinant(grid24):
    rng = np.random.default_rng(2)
    G = rng.normal(size=(12, 12))
    A = G @ G.T / 12 + 2 * np.eye(12)
    det = float(np.asarray(el.hpd_determinant(_dm(A, grid24))))
    assert abs(det - np.linalg.det(A)) / np.linalg.det(A) < 1e-12


@pytest.mark.slow
def test_condition(grid24):
    rng = np.random.default_rng(3)
    A = rng.normal(size=(12, 12))
    c2 = float(np.asarray(el.condition(_dm(A, grid24), "two")))
    assert abs(c2 - np.linalg.cond(A)) / np.linalg.cond(A) < 1e-10
    c1 = float(np.asarray(el.condition(_dm(A, grid24), "one")))
    assert abs(c1 - np.linalg.cond(A, 1)) / np.linalg.cond(A, 1) < 1e-10


def test_two_norm_estimate(grid24):
    """Run as ONE compiled program.  Called eagerly, forty iterations queue
    some five hundred small collective programs with no host read between
    them, and this jaxlib's CPU client deadlocks on a deep queue of those
    (a rendezvous one participant never joins; XLA aborts the process after
    40 s): always at 600 unsynchronised eager ``redistribute`` calls, and
    at this test's depth whenever the host is loaded enough for dispatch to
    outrun execution, which is how a worker of the six-worker run died."""
    rng = np.random.default_rng(4)
    A = rng.normal(size=(16, 10))
    estimate = jax.jit(functools.partial(el.two_norm_estimate, iters=40))
    est = float(np.asarray(estimate(_dm(A, grid24))))
    ref = np.linalg.norm(A, 2)
    assert abs(est - ref) / ref < 1e-6


def test_matrix_inertia(grid24):
    rng = np.random.default_rng(5)
    G = rng.normal(size=(14, 14))
    A = (G + G.T) / 2
    npos, nneg, nzero = el.lapack.matrix_inertia(_dm(A, grid24), nb=8)
    w = np.linalg.eigvalsh(A)
    assert (npos, nneg) == (int((w > 0).sum()), int((w < 0).sum()))


@pytest.mark.slow
def test_schatten_norms(grid24):
    rng = np.random.default_rng(6)
    A = rng.normal(size=(12, 9))
    s = np.linalg.svd(A, compute_uv=False)
    assert abs(float(np.asarray(el.nuclear_norm(_dm(A, grid24)))) - s.sum()) < 1e-10
    assert abs(float(np.asarray(el.two_norm(_dm(A, grid24)))) - s[0]) < 1e-11
    p3 = float(np.asarray(el.schatten_norm(_dm(A, grid24), 3.0)))
    assert abs(p3 - (s ** 3).sum() ** (1 / 3)) < 1e-10
