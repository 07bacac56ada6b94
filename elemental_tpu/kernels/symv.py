"""One-pass triangle ``symv`` (ISSUE 44): ``y = (tril(A) + stril(A)^T) x``
from ONE read of the stored lower triangle.

The tridiagonalization's column loop multiplies the panel's fixed
trailing view by a vector once a column.  XLA reads a full square for
that (the view mirrored once a panel, then a ``gemv``): twice the bytes
a one-stage reduction has to read, and the phase is bound by HBM.  Using
a tile for ``A_ij x_j`` AND for ``A_ij^T x_i`` in one visit is not
something the compiler fuses by itself; this kernel does.

The kernel reads ``A`` THROUGH ITS TRANSPOSE, ``B = A^T``, whose upper
triangle holds the stored entries: the TPU compiler holds the
eigensolve's working matrix column-major, so ``B`` is the same bytes
row-major, the layout a kernel's operand has (handed ``A`` itself, the
compiler re-laid every panel's view: ``tests/test_chip_compile.py``).  A
caller whose ``A`` is row-major pays one transposing copy.

The grid is ONE dimension over the square tiles of ``B`` on or above the
diagonal, block row by block row, from a scalar-prefetched table
``(i, j)``: a tile of the other triangle is never fetched.  Per
tile, on the VPU in the operand's own precision (float32 products and
sums):

* ``y_i += B_ij x_j``: the product against ``x_j`` as a lane-dense row
  (broadcast along sublanes), its 128-lane chunks summed into a
  ``(tile, 128)`` accumulator that stays in VMEM for the whole block row
  and is reduced along lanes ONCE, at the row's last tile;
* ``y_j += B_ij^T x_i``: the product against ``x_i`` as a column, held
  broadcast along lanes in a ``(tile, 128)`` scratch made once a block
  row, reduced along sublanes into block ``j`` of the result.

The result stays resident in VMEM over the whole grid (it runs in order
on the one core: ``dimension_semantics`` ``arbitrary``) and is written
once; vector and result are blocked ``(blocks, tile)``, so that a block
is a dynamic SUBLANE index.  The diagonal tiles, and those of a last
block column that overhangs ``nt``, take a second body that SELECTS on
``row <= col`` / ``row < col`` and ``col < nt`` (never a multiply):
whatever ``A`` holds above its diagonal, or an edge block in its padding,
cannot reach ``y``.

**The shard form (ISSUE 52): the same body on each chip of a square
grid.**  Chip ``(p, q)`` of an ``r x r`` grid holds ``A[i, j] = G[p + r i,
q + r j]`` of the zero-aligned ``(nt, nt)`` view ``G``; its stored entries
(global row >= global column) are a LOCAL lower triangle, ``i > j``, with
the local diagonal stored where ``p >= q``.  The two products of a visit
are then owed to two different residues of ``G v``, against two different
cuts of ``v``: ``sum_j A_ij v[q + r j]`` to rows ``p::r`` and ``sum_i A_ij
v[p + r i]`` to rows ``q::r`` (the reference's two accumulators,
``[MC,STAR]`` and ``[MR,STAR]``).  :func:`symv_lower_shard` keeps vector
and result in RESIDUE-MAJOR order, ``r`` blocks of whole tiles, so that
both cuts and both places are block rows the kernel indexes by ``p`` and
``q`` (scalar-prefetched: ``lax.axis_index`` values under ``shard_map``)
and nothing is cut or placed outside it; summed over the grid the results
are ``G v``.  What differs from one chip is parameters, not the algorithm:

* the diagonal tiles' rule compares GLOBAL indices (``q + r row <= p + r
  col`` for the one product, ``<`` for the other): ``G``'s diagonal, on
  the chips ``p == q``, counts in one product; a shard's diagonal is kept
  in both where ``p > q`` and dropped in both where ``p < q``; ``nt`` is
  the global order, so a chip of the last residues drops its line past an
  ``nt`` that is no multiple of ``r``.  One chip is ``r = 1``, ``p = q =
  0``, and traces to the body it had (``tests/test_chip_compile.py``);
* the orientation.  One chip reads the view through its transpose
  (above).  The grid's working shard the TPU compiler holds ROW-major
  (read in the rehearsal for a described ``v5e:2x2``: through the
  transpose every panel paid a transposing copy of its local view), so
  the shard form is handed the shard AS STORED and walks the tiles on or
  BELOW its diagonal, a block row from the first block column to the
  diagonal; rows and columns swap roles and nothing else changes.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import (LANE, compiler_params, interpret_default,
                     kernel_trace, round_up)

#: the order of the square tile a visit reads: 1 MiB of float32.  On a
#: v5e the read hides a grid step's fixed cost from 256 x 1024 up, and the
#: smallest square that does wastes the least on the diagonal (half of a
#: diagonal tile is read for nothing)
TILE = 512

def _tiles(nb: int, lower: bool = False):
    """The table the grid walks: ``(i, j)`` of every tile on or above the
    diagonal of ``nb x nb`` tiles, block row by block row: a block row
    begins on the diagonal and ends in the last block column.  ``lower``:
    the tiles on or BELOW it, a block row from the first block column to
    the diagonal."""
    return np.asarray([(i, j) for i in range(nb)
                       for j in (range(i + 1) if lower else range(i, nb))],
                      np.int32).T


def _symv_kernel(*refs, nt, tile, stride, lower):
    """One body for one chip and for a shard of a square grid.  ``stride``
    is the grid's order ``r`` (1: one chip) and ``nt`` the GLOBAL order.
    With ``stride > 1`` two more scalar-prefetched words hold the shard's
    ``(p, q)``, and vector and result are ``r`` residue blocks of ``nb``
    block rows each: the shard reads blocks ``q`` and ``p`` of the one and
    adds into blocks ``p`` and ``q`` of the other.  ``lower`` says which
    triangle of the array it is handed holds the stored entries: the upper
    one of the view's transpose (one chip), or the lower one of the view
    itself."""
    if stride > 1:
        ti_ref, tj_ref, p_ref, q_ref, a_ref, x_ref, y_ref, racc, xcol, xrow \
            = refs
        # the residues of the array's rows and of its columns
        rres, cres = ((p_ref[0], q_ref[0]) if lower
                      else (q_ref[0], p_ref[0]))
    else:
        ti_ref, tj_ref, a_ref, x_ref, y_ref, racc, xcol, xrow = refs
    nb = y_ref.shape[0] // stride
    t = pl.program_id(0)
    i, j = ti_ref[t], tj_ref[t]
    # where block row i of the rows' residue block and block row j of the
    # columns' lie in vector and result
    ir, jc = (rres * nb + i, cres * nb + j) if stride > 1 else (i, j)
    dt = a_ref.dtype
    diag = j == i
    first, last = (j == 0, diag) if lower else (diag, j == nb - 1)
    # a diagonal tile holds entries of the other triangle; where the local
    # order is no multiple of the tile, the last block column (``lower``:
    # row) holds padding past the array (and, where nt is no multiple of
    # the grid's order, a line past the matrix on the last residues' chips)
    ragged = -(-nt // stride) % tile or nt % stride
    masked = diag | (i == nb - 1 if lower else last) if ragged else diag

    @pl.when(t == 0)
    def _():
        y_ref[...] = jnp.zeros(y_ref.shape, dt)

    @pl.when(first)
    def _():
        # a block row begins: x_i as a column, broadcast along lanes
        racc[...] = jnp.zeros(racc.shape, dt)
        xcol[...] = jnp.broadcast_to(x_ref[pl.ds(ir, 1), :], (LANE, tile)).T

    def visit(masking: bool):
        # (a row at a dynamic sublane offset is loaded at its full width,
        # and a chunk of it broadcasts along sublanes only from a ref)
        xrow[...] = x_ref[pl.ds(jc, 1), :]
        xc = xcol[...]
        rsum = racc[...]
        csums = []
        if masking:
            row = i * tile + lax.broadcasted_iota(jnp.int32, (tile, LANE), 0)
            lane = j * tile + lax.broadcasted_iota(jnp.int32, (tile, LANE), 1)
            if stride > 1:
                # GLOBAL indices, of the view or of its transpose
                row = rres + stride * row
                lane = cres + stride * lane
        for c in range(tile // LANE):
            chunk = slice(c * LANE, (c + 1) * LANE)
            a = a_ref[:, chunk]
            stored = strict = a
            if masking:
                col = lane + c * LANE * stride
                # the stored triangle, short of the edge's padding
                if lower:
                    stored = jnp.where((col <= row) & (row < nt), a, 0)
                    strict = jnp.where((col < row) & (row < nt), a, 0)
                else:
                    stored = jnp.where((row <= col) & (col < nt), a, 0)
                    strict = jnp.where((row < col) & (col < nt), a, 0)
            rsum = rsum + stored * xrow[:, chunk]
            csums.append(jnp.sum(strict * xc, axis=0, keepdims=True))
        racc[...] = rsum
        y_ref[pl.ds(jc, 1), :] += jnp.concatenate(csums, axis=1)

    pl.when(jnp.logical_not(masked))(functools.partial(visit, False))
    pl.when(masked)(functools.partial(visit, True))

    @pl.when(last)
    def _():
        y_ref[pl.ds(ir, 1), :] += jnp.sum(racc[...].T, axis=0, keepdims=True)


def _launch(A, xb, *pq, nt, tile, stride, lower, interpret):
    """The one ``pallas_call`` of both entries: ``A`` the array the kernel
    reads (the stored entries in its lower triangle if ``lower``, in its
    upper), ``xb`` the vector blocked ``(blocks, tile)``, ``pq`` the
    shard's two scalar-prefetched coordinates (none on one chip)."""
    ti, tj = _tiles(-(-A.shape[0] // tile), lower)
    tables = [jnp.asarray(ti), jnp.asarray(tj), *pq]
    vector = pl.BlockSpec(xb.shape, lambda t, *tables: (0, 0))
    interpret = interpret_default(interpret)
    with kernel_trace(interpret):
        return pl.pallas_call(
            functools.partial(_symv_kernel, nt=nt, tile=tile, stride=stride,
                              lower=lower),
            out_shape=jax.ShapeDtypeStruct(xb.shape, A.dtype),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(tables),
                grid=(ti.shape[0],),
                in_specs=[
                    pl.BlockSpec((tile, tile),
                                 lambda t, ti, tj, *pq: (ti[t], tj[t])),
                    vector],
                out_specs=vector,
                scratch_shapes=[pltpu.VMEM((tile, LANE), A.dtype),
                                pltpu.VMEM((tile, LANE), A.dtype),
                                pltpu.VMEM((1, tile), A.dtype)]),
            compiler_params=compiler_params(("arbitrary",)),
            interpret=interpret,
            name="el_symv_lower",
        )(*tables, A, xb)


def shard_block(nt: int, stride: int, tile: int = TILE):
    """``(block, tile)`` of the kernel at global order ``nt`` on a ``stride
    x stride`` grid (1: one chip): the length a residue block of its vector
    is padded to (the local order ``ceil(nt / stride)`` rounded up to whole
    tiles) and the tile's order, clipped to the local order."""
    m = -(-nt // stride)
    tile = min(round_up(tile, LANE), round_up(m, LANE))
    return round_up(m, tile), tile


def symv_lower(A, x, *, tile: int = TILE, interpret=None):
    """``(tril(A) + stril(A)^T) x`` for a real ``(nt, nt)`` array ``A`` and
    an ``(nt,)`` vector ``x``: the product with the symmetric matrix whose
    lower triangle, diagonal included, ``A`` stores.  Every stored entry is
    read once; nothing above the diagonal reaches the result.  ``tile`` is
    the order of the square tile (a multiple of 128, clipped to ``nt``)."""
    nt = A.shape[0]
    if A.shape != (nt, nt) or x.shape != (nt,):
        raise ValueError(f"symv_lower needs (nt, nt) and (nt,), got "
                         f"{A.shape} and {x.shape}")
    if jnp.issubdtype(A.dtype, jnp.complexfloating):
        raise ValueError("pallas symv_lower is real-only")
    block, tile = shard_block(nt, 1, tile)
    xb = jnp.pad(x.astype(A.dtype), (0, block - nt)).reshape(-1, tile)
    y = _launch(A.T, xb, nt=nt, tile=tile, stride=1, lower=False,
                interpret=interpret)
    return y.reshape(-1)[:nt]


def symv_lower_shard(A, x, p, q, *, stride: int, nt: int, tile: int = TILE,
                     interpret=None):
    """What chip ``(p, q)`` of a ``stride x stride`` grid owes to ``(tril(G)
    + stril(G)^T) v``, from ONE read of its shard's stored part.

    ``A`` is the chip's ``(m, m)`` element-cyclic shard of the zero-aligned
    ``(nt, nt)`` matrix ``G``, ``A[i, j] = G[p + r i, q + r j]``: its stored
    entries (global row >= global column) are ``i > j``, and ``i == j``
    where ``p >= q``.  ``x`` is ``v`` in RESIDUE-MAJOR order: ``r`` blocks
    of ``block`` entries (:func:`shard_block`), block ``s`` holding
    ``v[s::r]`` and then zeros.  The result has that form too and holds

    * in block ``p``, owed to ``(G v)[p::r]``: ``sum_j A[i, j] v[q + r j]``
      over the STRICTLY stored entries (``p + r i > q + r j``);
    * in block ``q``, owed to ``(G v)[q::r]``: ``sum_i A[i, j] v[p + r i]``
      over the stored entries, ``G``'s diagonal included (``p + r i >= q +
      r j``);

    their sum where ``p == q``, zeros elsewhere: summed over the grid the
    results ARE ``G v``, residue-major.  The same tile walk and the same
    body as :func:`symv_lower`, which is the case ``stride = 1``, ``p = q =
    0``; what differs is the diagonal tiles' rule, a comparison of GLOBAL
    indices (``p``, ``q`` reach the kernel through the scalar prefetch:
    they are ``lax.axis_index`` values under ``shard_map``), and which
    block of vector and result a block row reads and adds to."""
    m = A.shape[0]
    block, tile = shard_block(nt, stride, tile)
    if stride < 2:
        raise ValueError("symv_lower_shard is a grid's form (stride >= 2); "
                         "one chip calls symv_lower")
    if (A.shape != (m, m) or m != -(-nt // stride)
            or x.shape != (stride * block,)):
        raise ValueError(
            f"symv_lower_shard needs (m, m) with m = ceil({nt} / {stride}) "
            f"and ({stride * block},), got {A.shape} and {x.shape}")
    if jnp.issubdtype(A.dtype, jnp.complexfloating):
        raise ValueError("pallas symv_lower is real-only")
    pq = [jnp.asarray(s, jnp.int32).reshape(1) for s in (p, q)]
    y = _launch(A, x.astype(A.dtype).reshape(-1, tile), *pq, nt=nt, tile=tile,
                stride=stride, lower=True, interpret=interpret)
    return y.reshape(-1)
