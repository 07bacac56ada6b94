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

def _tiles(nb: int):
    """The table the grid walks: ``(i, j)`` of every tile on or above the
    diagonal of ``nb x nb`` tiles, block row by block row: a block row
    begins on the diagonal and ends in the last block column."""
    return np.asarray([(i, j) for i in range(nb) for j in range(i, nb)],
                      np.int32).T


def _symv_kernel(ti_ref, tj_ref, a_ref, x_ref, y_ref, racc, xcol, xrow, *,
                 nt, tile):
    t = pl.program_id(0)
    i, j = ti_ref[t], tj_ref[t]
    dt = a_ref.dtype
    first, last = j == i, j == y_ref.shape[0] - 1
    # a diagonal tile holds entries of the other triangle; where nt is no
    # multiple of the tile, the last block column holds columns of padding
    masked = first | last if nt % tile else first

    @pl.when(t == 0)
    def _():
        y_ref[...] = jnp.zeros(y_ref.shape, dt)

    @pl.when(first)
    def _():
        # a block row begins: x_i as a column, broadcast along lanes
        racc[...] = jnp.zeros(racc.shape, dt)
        xcol[...] = jnp.broadcast_to(x_ref[pl.ds(i, 1), :], (LANE, tile)).T

    def visit(masking: bool):
        # (a row at a dynamic sublane offset is loaded at its full width,
        # and a chunk of it broadcasts along sublanes only from a ref)
        xrow[...] = x_ref[pl.ds(j, 1), :]
        xc = xcol[...]
        rsum = racc[...]
        csums = []
        if masking:
            row = i * tile + lax.broadcasted_iota(jnp.int32, (tile, LANE), 0)
            lane = j * tile + lax.broadcasted_iota(jnp.int32, (tile, LANE), 1)
        for c in range(tile // LANE):
            chunk = slice(c * LANE, (c + 1) * LANE)
            a = a_ref[:, chunk]
            stored = strict = a
            if masking:
                col = lane + c * LANE
                # the stored triangle, short of the edge's padding
                stored = jnp.where((row <= col) & (col < nt), a, 0)
                strict = jnp.where((row < col) & (col < nt), a, 0)
            rsum = rsum + stored * xrow[:, chunk]
            csums.append(jnp.sum(strict * xc, axis=0, keepdims=True))
        racc[...] = rsum
        y_ref[pl.ds(j, 1), :] += jnp.concatenate(csums, axis=1)

    pl.when(jnp.logical_not(masked))(functools.partial(visit, False))
    pl.when(masked)(functools.partial(visit, True))

    @pl.when(last)
    def _():
        y_ref[pl.ds(i, 1), :] += jnp.sum(racc[...].T, axis=0, keepdims=True)


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
    tile = min(round_up(tile, LANE), round_up(nt, LANE))
    nb = -(-nt // tile)
    ti, tj = _tiles(nb)
    xb = jnp.pad(x.astype(A.dtype), (0, nb * tile - nt)).reshape(nb, tile)
    vector = pl.BlockSpec((nb, tile), lambda t, ti, tj: (0, 0))
    interpret = interpret_default(interpret)
    with kernel_trace(interpret):
        y = pl.pallas_call(
            functools.partial(_symv_kernel, nt=nt, tile=tile),
            out_shape=jax.ShapeDtypeStruct((nb, tile), A.dtype),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(ti.shape[0],),
                in_specs=[
                    pl.BlockSpec((tile, tile),
                                 lambda t, ti, tj: (ti[t], tj[t])),
                    vector],
                out_specs=vector,
                scratch_shapes=[pltpu.VMEM((tile, LANE), A.dtype),
                                pltpu.VMEM((tile, LANE), A.dtype),
                                pltpu.VMEM((1, tile), A.dtype)]),
            compiler_params=compiler_params(("arbitrary",)),
            interpret=interpret,
            name="el_symv_lower",
        )(jnp.asarray(ti), jnp.asarray(tj), A.T, xb)
    return y.reshape(-1)[:nt]
