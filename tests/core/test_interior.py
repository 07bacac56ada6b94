"""Interior (arbitrary-offset) extract/embed conformance.

Oracle: numpy slicing of the global array (the same known-f(i,j) style as
the redistribution conformance matrix, tests/core/test_redist.py).
"""
import numpy as np
import pytest

import elemental_tpu as el
from elemental_tpu.core.dist import MC, MR, VC, VR, STAR
from elemental_tpu.redist.interior import (interior_view, interior_update,
                                           grain_view, grain_update,
                                           vstack, hstack)


PAIRS = [(MC, MR), (MR, MC), (VC, STAR), (STAR, VR), (MC, STAR), (STAR, STAR)]


def _mat(m, n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(m, n)).astype(np.float64)


RANGES = [((0, 5), (0, 7)), ((3, 11), (2, 9)), ((1, 13), (5, 6)),
          ((7, 13), (0, 11)), ((5, 6), (10, 11))]


@pytest.mark.parametrize("pair", PAIRS, ids=lambda p: f"{p[0].value}_{p[1].value}")
def test_interior_view(any_grid, pair):
    m, n = 13, 11
    F = _mat(m, n)
    A = el.from_global(F, *pair, grid=any_grid)
    for rows, cols in RANGES:
        B = interior_view(A, rows, cols)
        assert B.dist == A.dist and (B.calign, B.ralign) == (0, 0)
        got = np.asarray(el.to_global(B))
        np.testing.assert_allclose(got, F[rows[0]:rows[1], cols[0]:cols[1]])
        # padding-is-zero invariant
        assert B.local.shape == (B.col_stride * B.local_rows,
                                 B.row_stride * B.local_cols)


@pytest.mark.parametrize("pair", PAIRS, ids=lambda p: f"{p[0].value}_{p[1].value}")
def test_interior_update(any_grid, pair):
    m, n = 13, 11
    F = _mat(m, n)
    A = el.from_global(F, *pair, grid=any_grid)
    for rows, cols in RANGES:
        h, w = rows[1] - rows[0], cols[1] - cols[0]
        G = _mat(h, w, seed=7)
        B = el.from_global(G, *pair, grid=any_grid)
        out = interior_update(A, B, (rows[0], cols[0]))
        ref = F.copy()
        ref[rows[0]:rows[1], cols[0]:cols[1]] = G
        np.testing.assert_allclose(np.asarray(el.to_global(out)), ref)


def test_view_update_roundtrip(grid24):
    F = _mat(17, 15, seed=3)
    A = el.from_global(F, MC, MR, grid=grid24)
    B = interior_view(A, (4, 12), (3, 14))
    out = interior_update(A, B, (4, 3))
    np.testing.assert_allclose(np.asarray(el.to_global(out)), F)


def test_stacks(grid24):
    F, G = _mat(9, 6), _mat(5, 6, seed=1)
    A = el.from_global(F, MC, MR, grid=grid24)
    B = el.from_global(G, MC, MR, grid=grid24)
    np.testing.assert_allclose(np.asarray(el.to_global(vstack(A, B))),
                               np.vstack([F, G]))
    H = _mat(9, 4, seed=2)
    C = el.from_global(H, MC, MR, grid=grid24)
    np.testing.assert_allclose(np.asarray(el.to_global(hstack(A, C))),
                               np.hstack([F, H]))


GRAIN_PAIRS = [(MC, MR), (MR, MC), (VC, STAR), (STAR, VR)]


@pytest.mark.parametrize("pair", GRAIN_PAIRS,
                         ids=lambda p: f"{p[0].value}_{p[1].value}")
def test_grain_view_and_update_at_a_traced_offset(any_grid, pair):
    """The block walk of ``tridiag_eig``'s rolled merges: a loop counter
    times a stride-grain block, read and written by ONE traced body."""
    import jax
    from jax import lax
    p = any_grid.size
    m, n, h, w = 6 * p, 4 * p, 2 * p, p
    F = _mat(m, n)
    A = el.from_global(F, *pair, grid=any_grid)

    def negate_diagonal_blocks(A):
        def body(k, A):
            B = grain_view(A, (k * h, k * w), (h, w))
            return grain_update(A, B.with_local(-B.local), (k * h, k * w))
        return lax.fori_loop(0, 3, body, A)

    out = jax.jit(negate_diagonal_blocks)(A)
    ref = F.copy()
    for k in range(3):
        ref[k * h:(k + 1) * h, k * w:(k + 1) * w] *= -1
    np.testing.assert_array_equal(np.asarray(el.to_global(out)), ref)
    B = grain_view(A, (2 * h, w), (h, 2 * w))
    assert B.dist == A.dist and (B.calign, B.ralign) == (0, 0)
    np.testing.assert_array_equal(np.asarray(el.to_global(B)),
                                  F[2 * h:3 * h, w:3 * w])
    np.testing.assert_array_equal(
        np.asarray(el.to_global(interior_view(A, (2 * h, 3 * h), (w, 3 * w)))),
        np.asarray(el.to_global(B)))


def test_grain_ops_refuse_a_static_block_off_the_grain(grid24):
    A = el.from_global(_mat(16, 16), MC, MR, grid=grid24)
    with pytest.raises(ValueError, match="off the grain"):
        grain_view(A, (1, 0), (4, 4))       # row stride is 2
    with pytest.raises(ValueError, match="off the grain"):
        grain_view(A, (0, 0), (4, 6))       # column stride is 4
    B = el.from_global(_mat(4, 4), MC, MR, grid=grid24)
    with pytest.raises(ValueError, match="off the grain"):
        grain_update(A, B, (0, 2))
