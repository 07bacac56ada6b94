"""The one-pass triangle ``symv`` kernel alone (ISSUE 44), interpreted on
the CPU: ``(tril(A) + stril(A)^T) x`` against float64 numpy over tile
geometries (one tile, a ragged last block row, the eigensolve cell's
nb-multiples), what lies above the diagonal never reaching the result,
the leading zeros of the column loop's vector, and the agreement with
``blas/level2.hemv('L', ...)`` on the same stored triangle.  Small sizes:
an interpreted grid step costs a millisecond here; the kernel's speed is
the chip's to say, its lowering ``tests/test_chip_compile.py``'s.

And the SHARD form of the same body (ISSUE 52): what chip ``(p, q)`` of a
square grid owes to the product, from its element-cyclic shard as stored,
for each ``(p, q)`` of 2x2 (the diagonal of a shard is stored where ``p >=
q`` and counts in ONE of the two products), and the whole ``shard_map``ped
matvec of ``lapack/condense.py`` on the CPU's 2x2 mesh.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import elemental_tpu as el
from elemental_tpu.blas.level2 import hemv
from elemental_tpu.kernels import symv_lower
from elemental_tpu.kernels.symv import _tiles, shard_block, symv_lower_shard

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


@pytest.mark.parametrize("lower", [False, True],
                         ids=["through-the-transpose", "as-stored"])
@pytest.mark.parametrize("nb", [1, 2, 5, 32])
def test_the_grid_walks_each_tile_of_the_triangle_once(nb, lower):
    """One chip's kernel reads ``A`` through its transpose: the stored
    triangle is the tiles on or ABOVE the transpose's diagonal.  A shard is
    read as stored: the tiles on or BELOW its own."""
    ti, tj = _tiles(nb, lower)
    assert len(ti) == nb * (nb + 1) // 2
    assert len({(int(i), int(j)) for i, j in zip(ti, tj)}) == len(ti)
    assert np.all(tj <= ti if lower else tj >= ti)
    # block row by block row, from the tile where the kernel zeroes the
    # row's lane-side accumulator (the diagonal's; as stored, the first
    # block column's) to the one where it reduces it (the last block
    # column's; as stored, the diagonal's)
    assert np.all(np.diff(ti) >= 0)
    for i in range(nb):
        assert [int(j) for j in tj[ti == i]] == list(
            range(i + 1) if lower else range(i, nb))


@pytest.mark.parametrize("A,x", [
    (np.zeros((4, 5), np.float32), np.zeros(4, np.float32)),
    (np.zeros((4, 4), np.float32), np.zeros(5, np.float32)),
    (np.zeros((4, 4), np.complex64), np.zeros(4, np.complex64))])
def test_symv_refuses_what_it_cannot_multiply(A, x):
    with pytest.raises(ValueError):
        symv_lower(jnp.asarray(A), jnp.asarray(x))


# ---------------------------------------------------------------------
# the shard form: chip (p, q) of an r x r grid (ISSUE 52)
# ---------------------------------------------------------------------

#: (nt, tile): a local order that is no multiple of the tile (257 = 2
#: tiles and a row); an odd nt (the chips of residue 1 hold a line less);
#: the cell's shape in small, 640 local at the shipped tile
SHARDS = [(514, 128), (513, 128), (1280, 512)]
GRID = [(0, 0), (0, 1), (1, 0), (1, 1)]
R = 2

_shard = jax.jit(symv_lower_shard, static_argnames=("stride", "nt", "tile"))


def _residue_major(v, block, r=R):
    out = np.zeros((r, block), v.dtype)
    for s in range(r):
        out[s, :len(v[s::r])] = v[s::r]
    return out.reshape(-1)


def _natural(y, nt, r=R):
    y = np.asarray(y).reshape(r, -1)
    out = np.zeros(nt, y.dtype)
    for s in range(r):
        out[s::r] = y[s, :len(out[s::r])]
    return out


def _shard_of(G, p, q, poison=np.nan, r=R):
    """Chip (p, q)'s storage of the zero-aligned G: its own entries, and
    ``poison`` in the padding past the matrix where nt is no multiple."""
    m = -(-G.shape[0] // r)
    pad = np.full((r * m, r * m), poison, G.dtype)
    pad[:G.shape[0], :G.shape[0]] = G
    return pad[p::r, q::r]


def shard(G, v, p, q, tile):
    nt = G.shape[0]
    block, _tile = shard_block(nt, R, tile)
    return _shard(jnp.asarray(_shard_of(G, p, q)),
                  jnp.asarray(_residue_major(v, block)), p, q, stride=R,
                  nt=nt, tile=tile)


@pytest.mark.parametrize("p,q", GRID)
@pytest.mark.parametrize("nt,tile", SHARDS)
def test_a_shard_owes_its_two_products(nt, tile, p, q):
    """Block ``p`` of the result: the shard's STRICTLY stored entries
    against ``v[q::r]``; block ``q``: its stored entries, G's diagonal
    among them, transposed against ``v[p::r]``.  Where p == q the shard's
    diagonal is G's and counts once (in block q = p); where p > q it is
    stored and counts in both products; where p < q it is not stored."""
    G, _S, v = _stored(nt, np.float64, seed=5)
    A = _shard_of(G, p, q, poison=0.0)
    m = A.shape[0]
    gi = p + R * np.arange(m)[:, None]
    gj = q + R * np.arange(m)[None, :]
    vp = np.zeros(R * m)
    vp[:nt] = v
    want = np.zeros((R, shard_block(nt, R, tile)[0]))
    want[p, :m] += np.where(gi > gj, A, 0) @ vp[q::R]
    want[q, :m] += np.where(gi >= gj, A, 0).T @ vp[p::R]
    got = np.asarray(shard(G, v, p, q, tile)).reshape(R, -1)
    assert np.abs(got - want).max() <= _tol(nt, np.float64)
    # nothing is owed to another residue, nor past the local order
    others = [s for s in range(R) if s not in (p, q)]
    assert not np.any(got[others]) and not np.any(got[:, m:])


@pytest.mark.parametrize("nt,tile,dtype", [
    *[(nt, tile, np.float32) for nt, tile in SHARDS], (514, 128, np.float64)])
def test_the_shards_sum_to_the_product(nt, tile, dtype):
    G, S, v = _stored(nt, dtype, seed=6)
    y = sum(np.asarray(shard(G, v, p, q, tile), np.float64) for p, q in GRID)
    assert np.abs(_natural(y, nt) - S @ v.astype(np.float64)).max() <= (
        _tol(nt, dtype))


@pytest.mark.parametrize("poison", [np.nan, 1e30])
@pytest.mark.parametrize("p,q", GRID)
def test_nothing_above_a_shards_stored_part_reaches_either_result(p, q,
                                                                  poison):
    """A shard's entries above G's diagonal (its local upper triangle, and
    its diagonal where p < q) and its padding past an odd nt are dropped
    by a SELECT in both products: no bit of the result changes."""
    nt, tile = 513, 128
    G, _S, v = _stored(nt, np.float32, seed=7)
    bad = G.copy()
    bad[np.triu_indices(nt, 1)] = poison
    clean = np.asarray(shard(G, v, p, q, tile))
    block, _tile = shard_block(nt, R, tile)
    dirty = np.asarray(_shard(
        jnp.asarray(_shard_of(bad, p, q, poison=poison)),
        jnp.asarray(_residue_major(v, block)), p, q, stride=R, nt=nt,
        tile=tile))
    assert np.all(np.isfinite(dirty))
    assert np.array_equal(clean, dirty)


@pytest.mark.parametrize("lead", [1, 130, 250])
def test_leading_zeros_give_the_true_subproblems_product_on_the_grid(lead):
    nt, tile = 514, 128
    G, S, v = _stored(nt, np.float64, seed=8)
    v[:lead] = 0
    y = _natural(sum(np.asarray(shard(G, v, p, q, tile)) for p, q in GRID),
                 nt)
    want = S[lead:, lead:] @ v[lead:]
    assert np.abs(y[lead:] - want).max() <= _tol(nt, np.float64)


@pytest.mark.parametrize("A,x,stride", [
    (np.zeros((4, 5), np.float32), np.zeros(256, np.float32), 2),
    (np.zeros((4, 4), np.float32), np.zeros(8, np.float32), 2),
    (np.zeros((4, 4), np.complex64), np.zeros(256, np.complex64), 2),
    (np.zeros((8, 8), np.float32), np.zeros(128, np.float32), 1)])
def test_the_shard_form_refuses_what_it_cannot_multiply(A, x, stride):
    with pytest.raises(ValueError):
        symv_lower_shard(jnp.asarray(A), jnp.asarray(x), 0, 0, stride=stride,
                         nt=8)


@pytest.mark.parametrize("n,dtype", [(300, np.float64), (77, np.float64),
                                     (300, np.float32)])
def test_the_grids_matvec_on_the_cpu_mesh(n, dtype):
    """The whole ``shard_map``ped matvec of the column loop
    (``condense._symv_grid``: each chip's kernel, interpreted, and the one
    ``psum``) on a 2x2 mesh of CPU devices, garbage above the diagonal:
    against ``hemv('L', ...)`` on the same stored triangle and against
    numpy on ``to_global``'s matrix."""
    from elemental_tpu.lapack.condense import (_natural as natural,
                                               _residue_major as reorder,
                                               _symv_grid)
    A, S, x = _stored(n, dtype, seed=9)
    A[np.triu_indices(n, 1)] = 7.0
    grid = el.Grid(jax.devices()[:4], height=2)
    Ad = el.from_global(A, el.MC, el.MR, grid=grid)
    block, _tile = shard_block(n, 2)

    @jax.jit
    def matvec(Ad, x):
        return natural(_symv_grid(Ad, reorder(x, 2, block), True), 2, n)

    got = np.asarray(matvec(Ad, jnp.asarray(x)), np.float64)
    low = np.tril(np.asarray(el.to_global(Ad), np.float64))
    assert np.abs(got - (low + np.tril(low, -1).T) @ x).max() <= _tol(n, dtype)
    want = hemv("L", Ad, el.from_global(x[:, None], el.MC, el.MR, grid=grid),
                precision=jax.lax.Precision.HIGHEST)
    assert np.abs(got - np.asarray(el.to_global(want))[:, 0]).max() <= (
        _tol(n, dtype))
