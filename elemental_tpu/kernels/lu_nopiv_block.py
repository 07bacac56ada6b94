"""Unpivoted LU of one square block, resident in VMEM (ISSUE 46): the
column recurrence of ``lapack.lu._lu_nopiv``'s unblocked loop as ONE
kernel launch.

The XLA twin (``unb`` inside ``_lu_nopiv``) is a ``lax.fori_loop`` whose
step is a divide, a column store and an outer-product subtract on a
block of a few hundred KB: three or four tiny ops and a re-laid carry,
4.2 us a column on a v5e, 32,768 columns in a row in the HPL-MxP cell
(PERF.md 5).  Nothing of that is bound by HBM or by the MXU.  Here the
block sits in VMEM for the whole recurrence and a step is a few passes
of the vector unit over it.

It is ``kernels/lu_panel.py``'s ``inner=0`` mode without the pivot
search, the row swap and the SMEM pivot output, and it is lowered the
same way: the state lives in the output ref, row ``j`` moves through a
dynamic sublane load, column ``j`` is read by a lane-masked reduction
and written by a lane-masked select (a dynamic LANE offset does not
lower), ONE ``fori_loop`` over the columns, every pass over the whole
tile-padded block.  The
arithmetic is the twin's: for each column ``j``, ``l = B[j+1:, j] /
B[j, j]`` (a true divide), ``B[j+1:, j] = l``, ``B[j+1:, j+1:] -= l *
B[j, j+1:]``, in the block's own precision on the VPU; whether ``a - l
u`` rounds once or twice is the backend's to decide, as it is for the
twin.

The block is zero-padded to tiles.  The padding never reaches a stored
entry: a padded column only ever receives ``l * 0``, a padded row only
its own ``0 / pivot``, and neither is read by a step of a real column.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import (compiler_params, interpret_default, kernel_trace,
                     loop32, pad_tiles)


def _lu_nopiv_kernel(b_ref, out_ref, *, n):
    mp, wp = out_ref.shape
    out_ref[...] = b_ref[...]
    ridx = lax.broadcasted_iota(jnp.int32, (mp, 1), 0)
    cidx = lax.broadcasted_iota(jnp.int32, (1, wp), 1)

    def body(j, carry):
        W = out_ref[...]
        rowj = out_ref[pl.ds(j, 1), :]
        at_j = cidx == j
        col = jnp.sum(jnp.where(at_j, W, 0), axis=1, keepdims=True)
        piv = jnp.sum(jnp.where(at_j, rowj, 0), axis=1, keepdims=True)
        below = ridx > j
        l = jnp.where(below, col / piv, 0)
        urow = jnp.where(cidx > j, rowj, 0)
        out_ref[...] = jnp.where(at_j & below, l, W - l * urow)
        return carry

    loop32(0, n, body)


def lu_nopiv_block(B, *, interpret=None):
    """Packed unpivoted ``L\\U`` of a real square block (unit-lower L below
    the diagonal, U on and above it): the twin of ``lapack.lu._lu_nopiv``'s
    unblocked loop, one launch, the block resident in VMEM.  No pivoting:
    the caller's operand must not need it."""
    n = B.shape[0]
    if B.shape != (n, n):
        raise ValueError(f"lu_nopiv_block needs a square block, got "
                         f"{B.shape}")
    if jnp.issubdtype(B.dtype, jnp.complexfloating):
        raise ValueError("pallas lu_nopiv_block is real-only; complex "
                         "blocks keep the XLA loop")
    Bp = pad_tiles(B)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    interpret = interpret_default(interpret)
    with kernel_trace(interpret):
        packed = pl.pallas_call(
            functools.partial(_lu_nopiv_kernel, n=n),
            out_shape=jax.ShapeDtypeStruct(Bp.shape, B.dtype),
            in_specs=[vmem],
            out_specs=vmem,
            compiler_params=compiler_params(),
            interpret=interpret,
            name="el_lu_nopiv_block",
        )(Bp)
    return packed[:n, :n]
