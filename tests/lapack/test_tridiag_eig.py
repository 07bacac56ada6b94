"""Cuppen D&C tridiagonal eigensolver oracles.

Reference test style (SURVEY.md §5): known-spectrum matrices (Wilkinson,
1-2-1 Toeplitz), residual ||T Z - Z diag(w)||/||T||, orthogonality
||I - Z^T Z||, agreement with the sequential oracle -- the analogs of the
checks around upstream ``external/pmrrr`` in
``tests/lapack_like/HermitianEig.cpp``.  Covers both the replicated batched
phase (n <= repl_max) and the distributed [MC,MR] phase (n > repl_max), and
the herm_eig wiring end-to-end.
"""
import jax
import numpy as np
import pytest

import elemental_tpu as el
from elemental_tpu.lapack.tridiag_eig import _place_blocks, tridiag_eig

from ..conftest import compiled


def _trid(d, e):
    return np.diag(d) + np.diag(e, 1) + np.diag(e, -1)


def _check(d, e, w, Z, tol=1e-10):
    n = len(d)
    T = _trid(d, e)
    w = np.asarray(w)
    wref = np.linalg.eigvalsh(T)
    assert np.abs(w - wref).max() / max(np.abs(wref).max(), 1) < tol
    if Z is not None:
        Zg = np.asarray(el.to_global(Z)) if not isinstance(Z, np.ndarray) \
            else Z
        assert np.linalg.norm(T @ Zg - Zg * w[None, :]) \
            / max(np.linalg.norm(T), 1) < tol
        assert np.linalg.norm(Zg.T @ Zg - np.eye(n)) < tol * n


def test_replicated_random():
    rng = np.random.default_rng(0)
    n = 300
    d, e = rng.standard_normal(n), rng.standard_normal(n - 1)
    w, Z = tridiag_eig(d, e, grid=None, vectors=True)
    _check(d, e, w, np.asarray(Z))


def test_values_only_matches_vectors_path():
    rng = np.random.default_rng(1)
    n = 260
    d, e = rng.standard_normal(n), rng.standard_normal(n - 1)
    w = tridiag_eig(d, e, grid=None, vectors=False)
    wref = np.linalg.eigvalsh(_trid(d, e))
    assert np.abs(np.asarray(w) - wref).max() < 1e-10


def test_wilkinson():
    """W21+ has pathologically close eigenvalue pairs -- the classic
    deflation stress (upstream gallery ``Wilkinson``)."""
    m = 10
    n = 2 * m + 1
    d = np.abs(np.arange(n) - m).astype(np.float64)
    e = np.ones(n - 1)
    w, Z = tridiag_eig(d, e, grid=None, vectors=True, leaf_max=8)
    _check(d, e, w, np.asarray(Z))


def test_toeplitz_121_known_spectrum():
    """tridiag(1,2,1) has eigenvalues 2 - 2 cos(k pi/(n+1)) exactly."""
    n = 128
    d, e = 2.0 * np.ones(n), np.ones(n - 1)
    w = tridiag_eig(d, e, grid=None, vectors=False, leaf_max=16)
    k = np.arange(1, n + 1)
    wref = 2.0 - 2.0 * np.cos(k * np.pi / (n + 1))
    assert np.abs(np.sort(np.asarray(w)) - np.sort(wref)).max() < 1e-10


def test_tiny_couplings_and_zero_e():
    """Zero off-diagonals (fully deflated case) must not 0/0."""
    n = 96
    d = np.linspace(-3, 5, n)
    e = np.zeros(n - 1)
    w = tridiag_eig(d, e, grid=None, vectors=False, leaf_max=16)
    assert np.abs(np.sort(np.asarray(w)) - np.sort(d)).max() < 1e-10


def test_distributed_phase(any_grid):
    """n > repl_max: merges run as [MC,MR] SUMMA gemms on every grid."""
    rng = np.random.default_rng(2)
    n = 350
    d, e = rng.standard_normal(n), rng.standard_normal(n - 1)
    w, Zd = tridiag_eig(d, e, grid=any_grid, vectors=True,
                        leaf_max=48, repl_max=128)
    _check(d, e, w, Zd, tol=1e-9)


@pytest.mark.parametrize("shape", [(2, 2), (2, 4)],
                         ids=lambda rc: f"grid{rc[0]}x{rc[1]}")
def test_every_rolled_level_runs_its_own_merge(shape):
    """Sixteen blocks of 32 at the hand-off: three levels of merges (8, 4,
    2) whose blocks lie on the grid's grain, each ONE merge in a
    ``fori_loop``, then the last merge alone.  The levels' loops carry the
    same two arrays, and ``fori_loop`` keeps the traced body of a function
    it has seen for a carry: handed ONE merge function for all levels it
    ran the first level's body, blocks of 32, at every level (residual 1;
    only a tree with two rolled levels or more shows it).  The counters
    read what the device runs: fifteen merges, two products each."""
    from elemental_tpu import obs
    grid = el.Grid(jax.devices()[:shape[0] * shape[1]], height=shape[0])
    rng = np.random.default_rng(7)
    n = 512
    d, e = rng.standard_normal(n), rng.standard_normal(n - 1)
    with obs.metrics_scope() as reg:
        w, Zd = jax.jit(lambda d, e: tridiag_eig(
            d, e, grid=grid, vectors=True, leaf_max=16, repl_max=32))(d, e)
    _check(d, e, w, Zd, tol=1e-9)
    assert reg.counter_value("dc_merge", kind="distributed") == 15
    assert sum(reg.counters("gemm_route").values()) == 30


@pytest.mark.parametrize("B,nm", [(4, 88), (3, 63)])
def test_hand_off_places_the_blocks_bit_for_bit(any_grid, B, nm):
    """The hand-off between the replicated levels and the distributed ones
    (ISSUE 42): the (B, nm, nm) batch comes out as numpy's block diagonal,
    bit for bit, at block sizes that are multiples of no grid stride (88 is
    n = 350's own at the hand-off), eagerly and as a compiled program."""
    Qb = np.random.default_rng(42).standard_normal(
        (B, nm, nm)).astype(np.float32)
    want = np.zeros((B * nm, B * nm), np.float32)
    for b in range(B):
        want[b * nm:(b + 1) * nm, b * nm:(b + 1) * nm] = Qb[b]
    for place in (_place_blocks,
                  jax.jit(_place_blocks, static_argnums=1)):
        Qd = place(Qb, any_grid)
        assert (Qd.cdist, Qd.rdist) == (el.MC, el.MR)
        assert Qd.gshape == (B * nm, B * nm)
        got = np.asarray(el.to_global(Qd))
        assert got.dtype == np.float32
        assert np.array_equal(got, want)


def test_herm_eig_dc_path(grid24):
    """herm_eig end-to-end through the D&C tridiagonal stage (dc_min=0
    forces it), including the distributed >repl_max phase."""
    rng = np.random.default_rng(3)
    n = 200
    G = rng.standard_normal((n, n))
    F = (G + G.T) / 2
    A = el.from_global(F, el.MC, el.MR, grid=grid24)
    w, Z = compiled(el.herm_eig, dc_min=0, repl_max=96)(A)
    wref = np.linalg.eigvalsh(F)
    assert np.abs(np.asarray(w) - wref).max() < 1e-9
    Zg = np.asarray(el.to_global(Z))
    assert np.linalg.norm(F @ Zg - Zg * np.asarray(w)[None, :]) \
        / np.linalg.norm(F) < 1e-10
    assert np.linalg.norm(Zg.T @ Zg - np.eye(n)) < 1e-10 * n


def test_herm_eig_dc_subset(grid24):
    rng = np.random.default_rng(4)
    n = 150
    G = rng.standard_normal((n, n))
    F = (G + G.T) / 2
    A = el.from_global(F, el.MC, el.MR, grid=grid24)
    w, Z = compiled(el.herm_eig, subset=("index", 10, 29), dc_min=0,
                    repl_max=64)(A)
    wref = np.linalg.eigvalsh(F)[10:30]
    assert np.abs(np.asarray(w) - wref).max() < 1e-9
    Zg = np.asarray(el.to_global(Z))
    assert Zg.shape == (n, 20)
    assert np.linalg.norm(F @ Zg - Zg * np.asarray(w)[None, :]) \
        / np.linalg.norm(F) < 1e-10


def test_herm_eig_dc_values_only(grid24):
    rng = np.random.default_rng(5)
    n = 180
    G = rng.standard_normal((n, n))
    F = (G + G.T) / 2
    A = el.from_global(F, el.MC, el.MR, grid=grid24)
    w = compiled(el.herm_eig, vectors=False, dc_min=0, repl_max=64)(A)
    wref = np.linalg.eigvalsh(F)
    assert np.abs(np.asarray(w) - wref).max() < 1e-9
