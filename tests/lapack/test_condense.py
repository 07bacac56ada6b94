"""HermitianTridiag / Hessenberg oracles.

Model: reference ``tests/lapack_like/HermitianTridiag.cpp`` -- residual
``||A - Q T Q^H||/||A||`` + orthogonality ``||I - Q^H Q||``, real & complex.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elemental_tpu import Grid, from_global, obs, to_global, MC, MR
from elemental_tpu.blas.level2 import hemv
from elemental_tpu.lapack import condense
from elemental_tpu.lapack.condense import (
    hermitian_tridiag, apply_q_herm_tridiag, hessenberg, apply_q_hessenberg)
from elemental_tpu.matrices.basic import identity

from ..conftest import compiled


def _herm(n, dtype, seed=0):
    rng = np.random.default_rng(seed)
    A = rng.standard_normal((n, n))
    if jnp.issubdtype(dtype, jnp.complexfloating):
        A = A + 1j * rng.standard_normal((n, n))
    A = (A + A.conj().T) / 2
    return A.astype(dtype)


def _tridiag_full(d, e):
    return np.diag(np.asarray(d)) + np.diag(np.asarray(e), -1) + np.diag(np.asarray(e), 1)


@pytest.mark.parametrize("dtype", [jnp.float64, pytest.param(jnp.complex128, marks=pytest.mark.slow)])
@pytest.mark.parametrize("n", [24, pytest.param(37, marks=pytest.mark.slow)])
def test_hermitian_tridiag(grid24, dtype, n):
    A = _herm(n, dtype)
    Ad = from_global(A, MC, MR, grid24)
    Ap, d, e, tau = compiled(hermitian_tridiag, nb=8)(Ad)
    T = _tridiag_full(d, e)
    # Q explicit via back-transform of the identity
    Q = compiled(apply_q_herm_tridiag, nb=8)(
        Ap, tau, identity(n, grid=grid24, dtype=dtype))
    Qg = np.asarray(to_global(Q))
    resid = np.linalg.norm(A - Qg @ T @ Qg.conj().T) / max(np.linalg.norm(A), 1)
    orth = np.linalg.norm(np.eye(n) - Qg.conj().T @ Qg)
    assert resid < 1e-12
    assert orth < 1e-12
    # eigenvalues preserved
    np.testing.assert_allclose(np.linalg.eigvalsh(T), np.linalg.eigvalsh(A),
                               rtol=1e-10, atol=1e-10)


def test_hermitian_tridiag_uplo_upper(grid24):
    n = 24
    A = _herm(n, jnp.float64, seed=3)
    # poison the lower strict triangle: 'U' must only read the upper
    Abad = A.copy()
    Abad[np.tril_indices(n, -1)] = 99.0
    Ad = from_global(Abad, MC, MR, grid24)
    Ap, d, e, tau = compiled(hermitian_tridiag, uplo="U", nb=8)(Ad)
    T = _tridiag_full(d, e)
    np.testing.assert_allclose(np.linalg.eigvalsh(T), np.linalg.eigvalsh(A),
                               rtol=1e-10, atol=1e-10)


# the once-a-panel mirror of the trailing view (ISSUE 38)

def _grid(name):
    return (Grid(jax.devices()[:1]) if name == "1x1"
            else Grid(jax.devices()[:4], height=2) if name == "2x2"
            else Grid(jax.devices(), height=2))


def _stored(result):
    """``(Ap, d, e, tau)`` on the host, of ``Ap`` the lower triangle only."""
    Ap, d, e, tau = result
    Ap = np.asarray(to_global(Ap))
    return [Ap[np.tril_indices(Ap.shape[0])], *map(np.asarray, (d, e, tau))]


@pytest.mark.parametrize("n,nb", [(24, 8), (37, 8), (40, 16), (17, 4),
                                  (9, 16)])
def test_one_mirror_a_panel(grid24, n, nb):
    """``herm_tridiag_symmetrize`` ticks once a panel, never once a column."""
    with obs.metrics_scope() as reg:
        compiled(hermitian_tridiag, nb=nb)(
            from_global(_herm(n, jnp.float64), MC, MR, grid24))
    (panels,) = reg.counters("herm_tridiag_panel").values()
    (mirrors,) = reg.counters("herm_tridiag_symmetrize").values()
    assert mirrors == panels == -(-(n - 1) // nb)


@pytest.mark.parametrize("poison", [np.nan, 1e30])
@pytest.mark.parametrize("dtype", [jnp.float64, jnp.complex128])
@pytest.mark.parametrize("grid_name", ["1x1", "2x4"])
def test_hermitian_tridiag_lower_never_reads_the_upper_triangle(
        grid_name, dtype, poison):
    """The twin of ``test_hermitian_tridiag_uplo_upper``: with ``uplo='L'``
    the mirror is built from stored entries only, so whatever stands above
    the diagonal changes no bit of ``d``, ``e``, ``tau`` or the packed
    lower triangle."""
    n, grid = 29, _grid(grid_name)
    A = _herm(n, dtype, seed=5)
    Abad = A.copy()
    Abad[np.triu_indices(n, 1)] = poison
    reduce = compiled(hermitian_tridiag, nb=8)
    clean = reduce(from_global(A, MC, MR, grid))
    dirty = reduce(from_global(Abad, MC, MR, grid))
    for want, got in zip(_stored(clean), _stored(dirty)):
        assert np.all(np.isfinite(got))
        assert np.array_equal(want, got)


@pytest.mark.parametrize("grid_name", ["1x1", "2x4"])
def test_mirror_keeps_the_stored_diagonal(grid_name, monkeypatch):
    """A Hermitian input whose diagonal carries a small imaginary part: the
    mirror takes the diagonal from the stored triangle, not from its
    conjugate, so the result is what the two-accumulator ``hemv`` on the
    lower triangle gave before (the column loop rebuilt around it here; a
    conjugated diagonal would move it by the imaginary part, 1e-3)."""
    n, grid = 26, _grid(grid_name)
    A = _herm(n, jnp.complex128, seed=7)
    A[np.diag_indices(n)] += 1e-3j * np.arange(1, n + 1)
    Ad = from_global(A, MC, MR, grid)
    got = hermitian_tridiag(Ad, nb=8)
    monkeypatch.setattr(condense, "gemv", lambda A, x, precision: hemv(
        "L", A, x, precision=precision))
    # a NEW function object: jax keys its trace cache by the function
    panel = condense._tridiag_panel.__wrapped__
    monkeypatch.setattr(condense, "_tridiag_panel", jax.jit(
        lambda *args: panel(*args), static_argnums=(2, 3, 4, 5, 6)))
    want = hermitian_tridiag(Ad, nb=8)
    for w, g in zip(_stored(want), _stored(got)):
        np.testing.assert_allclose(g, w, rtol=0, atol=1e-12 * np.abs(w).max())


# the paths through the one-pass triangle symv kernel: one chip (ISSUE 44)
# and a square grid, each chip on its own shard (ISSUE 52)

@pytest.mark.parametrize("n,nb,grid_name,impl", [
    (320, 64, "1x1", "symv"), (257, 64, "1x1", "symv"),
    (320, 64, "2x2", "symv_grid"), (257, 64, "2x2", "symv_grid"),
    (37, 8, "2x2", "symv_grid")])
def test_symv_path_gives_the_mirror_paths_reduction(n, nb, grid_name, impl,
                                                    monkeypatch):
    """Nothing mirrored, the kernel on the view as stored (on 2x2 each
    chip's on its own shard, the vectors of the loop in residue-major
    order, one ``psum`` a column): ``d``, ``e``, ``tau`` and the packed
    lower triangle are the mirror path's to rounding, odd orders (a chip of
    the last residue holds a line less) and a ragged last panel included.
    Float64, because the reduction amplifies a rounding along
    the columns (float32 moves the last ``d`` by 1e-3 here, and both
    tridiagonal matrices have ``A``'s eigenvalues to 1e-6: the test
    below); garbage above the diagonal must not matter on either path."""
    A = _herm(n, jnp.float64, seed=n)
    A[np.triu_indices(n, 1)] = np.nan
    Ad = from_global(A, MC, MR, _grid(grid_name))
    want = _stored(compiled(hermitian_tridiag, nb=nb)(Ad))
    # the path of a TPU; the grid is the CPU's, so the kernel is
    # interpreted.  (The choice is a static argument of the jitted panel:
    # nothing traced for the mirror path is handed back.)
    monkeypatch.setattr(condense, "_reads_triangle_once", lambda A: True)
    with obs.metrics_scope() as reg:
        got = _stored(compiled(hermitian_tridiag, nb=nb)(Ad))
    panels = -(-(n - 1) // nb)
    assert dict(reg.counters("herm_tridiag_hemv")) == {
        ("herm_tridiag_hemv", (("impl", impl),)): panels}
    assert not reg.counters("herm_tridiag_symmetrize")
    for w, g in zip(want, got):
        np.testing.assert_allclose(g, w, rtol=0, atol=1e-9 * np.abs(w).max())


def test_the_grid_path_reads_the_upper_triangle_too(monkeypatch):
    """``uplo='U'`` is transposed to lower first, on every path."""
    n = 44
    A = _herm(n, jnp.float64, seed=5)
    Abad = A.copy()
    Abad[np.tril_indices(n, -1)] = np.nan
    Ad = from_global(Abad, MC, MR, _grid("2x2"))
    monkeypatch.setattr(condense, "_reads_triangle_once", lambda A: True)
    _Ap, d, e, _tau = compiled(hermitian_tridiag, uplo="U", nb=8)(Ad)
    np.testing.assert_allclose(np.linalg.eigvalsh(_tridiag_full(d, e)),
                               np.linalg.eigvalsh(A), rtol=1e-10, atol=1e-10)


@pytest.mark.parametrize("grid_name", ["1x1", "2x2"])
def test_herm_eig_through_the_symv_path_agrees_with_numpy(grid_name,
                                                          monkeypatch):
    from elemental_tpu.lapack.spectral import herm_eig
    monkeypatch.setattr(condense, "_reads_triangle_once", lambda A: True)
    n = 129
    A = _herm(n, jnp.float32, seed=44)
    w = compiled(herm_eig, nb=64, vectors=False)(
        from_global(A, MC, MR, _grid(grid_name)))
    want = np.linalg.eigvalsh(A.astype(np.float64))
    assert np.abs(np.asarray(w, np.float64) - want).max() <= (
        50 * np.finfo(np.float32).eps * np.abs(want).max())


@pytest.mark.parametrize("shape,platform,dtype,want", [
    ((1, 1), "tpu", jnp.float32, "symv"),
    ((2, 2), "tpu", jnp.float32, "symv_grid"),   # a shard's stored part is
    ((4, 4), "tpu", jnp.float32, "symv_grid"),   # a local lower triangle
    ((2, 4), "tpu", jnp.float32, "mirror"),      # ... a trapezoid
    ((1, 4), "tpu", jnp.float32, "mirror"),
    ((4, 1), "tpu", jnp.float32, "mirror"),
    ((1, 1), "cpu", jnp.float32, "mirror"),  # an interpreted kernel a column
    ((2, 2), "cpu", jnp.float32, "mirror"),
    ((1, 1), "tpu", jnp.float64, "mirror"),
    ((2, 2), "tpu", jnp.float64, "mirror"),
    ((1, 1), "tpu", jnp.complex64, "mirror"),    # Mosaic has no complex type
    ((2, 2), "tpu", jnp.complex64, "mirror"),
    ((1, 1), "tpu", jnp.bfloat16, "mirror")])
def test_the_rule_reads_the_grid_and_the_dtype(shape, platform, dtype, want):
    from types import SimpleNamespace as NS
    chips = shape[0] * shape[1]
    A = NS(grid=NS(size=chips, height=shape[0], width=shape[1],
                   devices=[NS(platform=platform)] * chips),
           dtype=jnp.dtype(dtype))
    assert condense._reads_triangle_once(A) is (want != "mirror")
    assert condense._hemv_impl(A) == want


@pytest.mark.parametrize("dtype", [jnp.float64, jnp.complex128])
def test_hessenberg(grid24, dtype):
    n = 21
    rng = np.random.default_rng(7)
    A = rng.standard_normal((n, n))
    if jnp.issubdtype(dtype, jnp.complexfloating):
        A = A + 1j * rng.standard_normal((n, n))
    A = A.astype(dtype)
    Ad = from_global(A, MC, MR, grid24)
    H, Qp, tau = compiled(hessenberg)(Ad)
    Hg = np.asarray(to_global(H))
    assert np.abs(np.tril(Hg, -2)).max() < 1e-12
    Q = compiled(apply_q_hessenberg)(
        Qp, tau, identity(n, grid=grid24, dtype=dtype))
    Qg = np.asarray(to_global(Q))
    resid = np.linalg.norm(A - Qg @ Hg @ Qg.conj().T) / np.linalg.norm(A)
    orth = np.linalg.norm(np.eye(n) - Qg.conj().T @ Qg)
    assert resid < 1e-12
    assert orth < 1e-12


# ---------------------------------------------------------------------
# Bidiag (the SVD condense step)
# ---------------------------------------------------------------------

def _check_bidiag(F, grid, nb):
    import elemental_tpu as el
    from elemental_tpu.lapack.condense import bidiag, apply_p_bidiag
    from elemental_tpu.lapack.qr import apply_q
    m, n = F.shape
    A = el.from_global(F, el.MC, el.MR, grid=grid)
    Ap, d, e, tauq, taup = bidiag(A, nb=nb)
    dn, en = np.asarray(d), np.asarray(e)
    assert np.isrealobj(dn) and np.isrealobj(en)
    B = np.zeros((m, n), F.dtype)
    B[:n, :n] = np.diag(dn.astype(F.dtype)) + np.diag(en.astype(F.dtype), 1)
    I_m = el.from_global(np.eye(m, dtype=F.dtype), el.MC, el.MR, grid=grid)
    I_n = el.from_global(np.eye(n, dtype=F.dtype), el.MC, el.MR, grid=grid)
    Q = np.asarray(el.to_global(apply_q(Ap, tauq, I_m, orient="N")))
    P = np.asarray(el.to_global(apply_p_bidiag(Ap, taup, I_n, orient="N")))
    assert np.linalg.norm(Q.conj().T @ Q - np.eye(m)) < 1e-12 * m
    assert np.linalg.norm(P.conj().T @ P - np.eye(n)) < 1e-12 * n
    rec = Q @ B @ P.conj().T
    assert np.linalg.norm(rec - F) / np.linalg.norm(F) < 1e-13
    sa = np.linalg.svd(F, compute_uv=False)
    sb = np.linalg.svd(B, compute_uv=False)
    assert np.linalg.norm(sa - sb) < 1e-12 * max(sa[0], 1)


def test_bidiag_tall(grid24):
    rng = np.random.default_rng(20)
    _check_bidiag(rng.normal(size=(24, 16)), grid24, nb=8)


def test_bidiag_square_full_panel(grid24):
    rng = np.random.default_rng(21)
    _check_bidiag(rng.normal(size=(16, 16)), grid24, nb=16)


@pytest.mark.slow
def test_bidiag_complex(grid24):
    rng = np.random.default_rng(22)
    F = rng.normal(size=(20, 12)) + 1j * rng.normal(size=(20, 12))
    _check_bidiag(F, grid24, nb=4)


@pytest.mark.slow
def test_svd_golub_kahan(grid24):
    import elemental_tpu as el
    rng = np.random.default_rng(23)
    F = rng.normal(size=(32, 20))
    A = el.from_global(F, el.MC, el.MR, grid=grid24)
    U, s, V = el.svd(A, approach="golub")
    rec = np.asarray(el.to_global(U)) @ np.diag(np.asarray(s)) \
        @ np.asarray(el.to_global(V)).T
    assert np.linalg.norm(rec - F) / np.linalg.norm(F) < 1e-13
    assert np.allclose(np.asarray(s), np.linalg.svd(F, compute_uv=False),
                       atol=1e-12)
    # values-only + the scalable eig path
    s2 = el.svd(A, vectors=False, approach="golub", eig_approach="qdwh")
    assert np.allclose(np.asarray(s2), np.linalg.svd(F, compute_uv=False),
                       atol=1e-10)
