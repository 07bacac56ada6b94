"""The one-pass triangle ``symv`` kernel alone (ISSUE 44), interpreted on
the CPU: ``(tril(A) + stril(A)^T) x`` against float64 numpy over tile
geometries (one tile, a ragged last block row, the eigensolve cell's
nb-multiples), what lies above the diagonal never reaching the result,
the leading zeros of the column loop's vector, and the agreement with
``blas/level2.hemv('L', ...)`` on the same stored triangle.  Small sizes:
an interpreted grid step costs a millisecond here; the kernel's speed is
the chip's to say, its lowering ``tests/test_chip_compile.py``'s.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import elemental_tpu as el
from elemental_tpu.blas.level2 import hemv
from elemental_tpu.kernels import symv_lower
from elemental_tpu.kernels.symv import _tiles

#: (nt, tile): one tile larger than the matrix; nt not a multiple of the
#: tile (a ragged last block row); five block rows that divide evenly; the
#: eigensolve cell's nb-multiples at the shipped tile
SMALL = [(100, 512), (257, 128), (640, 128)]
CELL = [(256, 512), (768, 512), (1280, 512)]

#: one compile for each (shapes, tile): an interpreted kernel's cost here
_symv = jax.jit(symv_lower, static_argnames=("tile",))


def symv(A, x, tile):
    return _symv(jnp.asarray(A), jnp.asarray(x), tile=tile)


def _stored(nt, dtype, seed=0):
    """(A as stored, the symmetric matrix its lower triangle stands for in
    float64, x): entries of size one, so y is of size sqrt(nt)."""
    rng = np.random.default_rng(seed + nt)
    A = rng.normal(size=(nt, nt)).astype(dtype)
    low = np.tril(A.astype(np.float64))
    return A, low + np.tril(low, -1).T, rng.normal(size=(nt,)).astype(dtype)


def _tol(nt, dtype):
    # a sum of nt products of size one, accumulated in the operand's type
    return 8 * np.finfo(dtype).eps * nt


@pytest.mark.parametrize("nt,tile,dtype", [
    *[(nt, tile, np.float32) for nt, tile in SMALL + CELL],
    (257, 128, np.float64), (768, 512, np.float64)])
def test_symv_agrees_with_float64_numpy(nt, tile, dtype):
    A, S, x = _stored(nt, dtype)
    y = symv(A, x, tile)
    assert y.shape == (nt,) and y.dtype == dtype
    assert np.abs(np.asarray(y, np.float64)
                  - S @ x.astype(np.float64)).max() <= _tol(nt, dtype)


@pytest.mark.parametrize("poison", [np.nan, 1e30])
@pytest.mark.parametrize("nt,tile", SMALL)
def test_nothing_above_the_diagonal_reaches_y(nt, tile, poison):
    """The diagonal tiles are masked by a SELECT: a NaN (or a 1e30, whose
    product with a zero weight would still be finite but wrong in the sum)
    above the diagonal changes no bit of the result."""
    A, _S, x = _stored(nt, np.float32, seed=1)
    bad = A.copy()
    bad[np.triu_indices(nt, 1)] = poison
    clean = np.asarray(symv(A, x, tile))
    dirty = np.asarray(symv(bad, x, tile))
    assert np.all(np.isfinite(dirty))
    assert np.array_equal(clean, dirty)


@pytest.mark.parametrize("lead", [1, 130, 250])
@pytest.mark.parametrize("nt,tile", [(257, 128), (768, 512)])
def test_leading_zeros_give_the_true_subproblems_product(nt, tile, lead):
    """The column loop multiplies the panel's FIXED view by a vector whose
    first entries are zero: rows ``lead:`` of the result are the product of
    the trailing principal submatrix with the vector's tail."""
    A, S, x = _stored(nt, np.float64, seed=2)
    x[:lead] = 0
    y = np.asarray(symv(A, x, tile))
    want = S[lead:, lead:] @ x[lead:]
    assert np.abs(y[lead:] - want).max() <= _tol(nt, np.float64)


@pytest.mark.parametrize("nt,tile,dtype", [(257, 128, np.float32),
                                           (768, 512, np.float64)])
def test_symv_equals_level2_hemv_on_the_stored_triangle(nt, tile, dtype):
    A, _S, x = _stored(nt, dtype, seed=3)
    A[np.triu_indices(nt, 1)] = 7.0          # hemv('L') never reads it either
    grid = el.Grid(jax.devices()[:1])
    want = hemv("L", el.from_global(A, el.MC, el.MR, grid=grid),
                el.from_global(x[:, None], el.MC, el.MR, grid=grid),
                precision=jax.lax.Precision.HIGHEST)
    want = np.asarray(el.to_global(want))[:, 0]
    got = np.asarray(symv(A, x, tile))
    assert np.abs(got - want).max() <= _tol(nt, dtype)


@pytest.mark.parametrize("nb", [1, 2, 5, 32])
def test_the_grid_walks_each_tile_of_the_triangle_once(nb):
    """The kernel reads ``A`` through its transpose: the stored triangle is
    the tiles on or ABOVE the transpose's diagonal."""
    ti, tj = _tiles(nb)
    assert len(ti) == nb * (nb + 1) // 2
    assert len({(int(i), int(j)) for i, j in zip(ti, tj)}) == len(ti)
    assert np.all(tj >= ti)
    # block row by block row, from the diagonal tile (where the kernel
    # zeroes the row's lane-side accumulator) to the last block column
    # (where it reduces it)
    assert np.all(np.diff(ti) >= 0)
    for i in range(nb):
        assert [int(j) for j in tj[ti == i]] == list(range(i, nb))


@pytest.mark.parametrize("A,x", [
    (np.zeros((4, 5), np.float32), np.zeros(4, np.float32)),
    (np.zeros((4, 4), np.float32), np.zeros(5, np.float32)),
    (np.zeros((4, 4), np.complex64), np.zeros(4, np.complex64))])
def test_symv_refuses_what_it_cannot_multiply(A, x):
    with pytest.raises(ValueError):
        symv_lower(jnp.asarray(A), jnp.asarray(x))
