"""Fused Pallas LU panel: pivot search, row swap, column scale, and the
rank-1 / chunk-blocked trailing updates in ONE kernel launch.

The XLA path (``lapack.lu._panel_lu``) lowers each column step to a
handful of small ops -- argmax, two row gathers, two scatters, a divide,
an outer product -- and the ``_INNERS`` chunk ladder adds a
triangular-solve + matmul pair per chunk.  At nb = 256 that is O(10^3)
tiny kernels on the factorization's serial spine.  Here the whole panel
sits in VMEM and the column recurrence is a single ``lax.fori_loop``
inside one ``pallas_call``; the packed L\\U factor and the pivot
sequence come back in one store each.

The body works IN PLACE on the output ref, in the forms the TPU compiler
lowers: rows move through dynamic sublane loads/stores
(``ref[pl.ds(j, 1), :]``), a column is read by a lane-masked reduction
and written back by a lane-masked select (a dynamic LANE offset does not
lower), every static window starts on an (8, 128) tile boundary, and the
pivot search is a max followed by a first-index min (same first-max
tie-break as ``jnp.argmax``).

Two modes, selected by the static ``inner`` width:

* ``inner=0`` -- the unblocked twin of ``_panel_lu_unb``: same candidate
  mask, same first-max tie-break, same divide and rank-1 update, so the
  pivot sequence is identical and the packed factor agrees to rounding
  (bit-for-bit where the backend rounds the twin's ops the same way).
* ``inner=k`` -- the in-kernel analog of the ``_INNERS`` chunk ladder:
  within a chunk the per-column rank-1 updates reach the chunk's own
  columns only; the chunk then ends with the unit-diagonal forward
  substitution U12 = L11^{-1} A12 (as rank-1 updates of the chunk's
  rows) and one MXU-shaped trailing ``dot`` A22 -= L21 @ U12.  Same math
  as the ladder's ``triangular_solve`` + matmul pair, different
  summation order -- residual-bounded; the pivot search still sees a
  fully updated column, so the pivot sequence is the unblocked one.

Pivot indices are returned as the per-step swap sequence (LAPACK ipiv
convention, absolute panel rows) through SMEM; the composed permutation
is replayed OUTSIDE the kernel by the exact bookkeeping
``_panel_lu_unb`` does on ``perm``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import (LANE, SUBLANE, compiler_params, interpret_default,
                     kernel_trace, loop32, round_up)


def _lu_panel_kernel(p_ref, out_ref, piv_ref, *, m, nbw, inner, precision):
    mp, wp = out_ref.shape
    dt = out_ref.dtype
    out_ref[...] = p_ref[...]
    neg = jnp.asarray(-jnp.inf, dt)
    cfull = lax.broadcasted_iota(jnp.int32, (1, wp), 1)

    def col_steps(s, e, hi):
        # factor columns [s, e) in place.  Nothing above row s changes
        # any more, so the passes run on the static row window that
        # starts at the tile boundary at or above s (full width: a row
        # loaded at a dynamic sublane offset cannot also start at a lane
        # offset).  The rank-1 update reaches columns (j, hi): hi == wp
        # is the unblocked twin, hi == e the chunk-blocked mode.
        r0 = s // SUBLANE * SUBLANE
        ridx = lax.broadcasted_iota(jnp.int32, (mp - r0, 1), 0) + r0

        def body(j, carry):
            W = out_ref[r0:, :]
            col = jnp.sum(jnp.where(cfull == j, W, 0), axis=1, keepdims=True)
            cand = jnp.where((ridx >= j) & (ridx < m), jnp.abs(col), neg)
            p = jnp.min(jnp.where(cand == jnp.max(cand), ridx, mp))
            piv_ref[j] = p
            rowj = out_ref[pl.ds(j, 1), :]
            rowp = out_ref[pl.ds(p, 1), :]
            out_ref[pl.ds(j, 1), :] = rowp
            out_ref[pl.ds(p, 1), :] = rowj
            pivval = jnp.sum(jnp.where(cfull == j, rowp, 0), axis=1,
                             keepdims=True)
            colj = jnp.sum(jnp.where(cfull == j, rowj, 0), axis=1,
                           keepdims=True)
            col = jnp.where(ridx == p, colj, col)
            l = jnp.where(ridx > j, col / pivval, jnp.zeros_like(col))
            live = (cfull > j) & (cfull < hi)
            W = out_ref[r0:, :]
            W = W - jnp.where(live, l * out_ref[pl.ds(j, 1), :], 0)
            out_ref[r0:, :] = jnp.where((cfull == j) & (ridx > j), l, W)
            return carry

        loop32(s, e, body)

    if inner <= 0 or inner >= nbw:
        col_steps(0, nbw, wp)
    else:
        for s in range(0, nbw, inner):
            e = min(s + inner, nbw)
            if e >= nbw:
                col_steps(s, e, wp)
                break
            col_steps(s, e, e)
            # chunk tail, fused: U12 = L11^{-1} A12 by unit-diagonal
            # forward substitution (the ladder's triangular_solve) as w
            # rank-1 updates of the chunk's rows, then one MXU trailing
            # dot A22 -= L21 @ U12 (the ladder's matmul) -- both on the
            # VMEM-resident panel.  Operands are tile-aligned windows
            # around the chunk with everything outside it masked to zero.
            r0 = s // SUBLANE * SUBLANE
            k0, k1 = s // LANE * LANE, round_up(e, LANE)
            rk = lax.broadcasted_iota(jnp.int32, (k1 - k0, 1), 0) + k0
            ck = cfull[:, k0:k1]

            def sub_body(i, carry):
                Wk = out_ref[k0:k1, :]
                li = jnp.sum(jnp.where(cfull == i, Wk, 0), axis=1,
                             keepdims=True)
                li = jnp.where((rk > i) & (rk < e), li, 0)
                ui = jnp.where(cfull >= e, out_ref[pl.ds(i, 1), :], 0)
                out_ref[k0:k1, :] = Wk - li * ui
                return carry

            loop32(s, e, sub_body)
            ridx = lax.broadcasted_iota(jnp.int32, (mp - r0, 1), 0) + r0
            L21 = jnp.where((ridx >= e) & (ck >= s) & (ck < e),
                            out_ref[r0:, k0:k1], 0)
            U12 = jnp.where((rk >= s) & (rk < e) & (cfull >= e),
                            out_ref[k0:k1, :], 0)
            out_ref[r0:, :] = out_ref[r0:, :] - jnp.dot(
                L21, U12, precision=precision,
                preferred_element_type=dt)


def lu_panel(P, nbw: int, precision=None, *, inner: int = 0,
             interpret=None):
    """Fused twin of ``lapack.lu._panel_lu``: one launch, same contract
    ``(packed L\\U, composed row permutation)``.

    Real dtypes only -- callers gate complex panels back to the XLA
    ladder (the dispatch in ``PanelPlan.use_pallas``); reaching here
    with a complex panel is a caller bug and raises loudly.
    """
    M, w = P.shape
    nbw = int(nbw)
    if jnp.issubdtype(P.dtype, jnp.complexfloating):
        raise ValueError("pallas LU panel is real-only; the panel_impl "
                         "dispatch falls back to xla for complex dtypes")
    # the chunk tail reads rows [k0, k1) of the panel as U12's window, so
    # the padded height covers the padded width
    wp = round_up(w, LANE)
    mp = max(round_up(M, SUBLANE), wp)
    Pp = jnp.pad(P, ((0, mp - M), (0, wp - w)))
    kern = functools.partial(_lu_panel_kernel, m=M, nbw=nbw,
                             inner=int(inner),
                             precision=lax.Precision.HIGHEST
                             if precision is None else precision)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    interpret = interpret_default(interpret)
    with kernel_trace(interpret):
        packed, piv = pl.pallas_call(
            kern,
            out_shape=(jax.ShapeDtypeStruct((mp, wp), P.dtype),
                       jax.ShapeDtypeStruct((wp,), jnp.int32)),
            in_specs=[vmem],
            out_specs=(vmem, pl.BlockSpec(memory_space=pltpu.SMEM)),
            compiler_params=compiler_params(),
            interpret=interpret,
            name="el_lu_panel",
        )(Pp)
    packed = packed[:M, :w]

    # replay the per-step swap sequence into the composed permutation --
    # exactly the bookkeeping _panel_lu_unb does on `perm`, hoisted out
    # of the kernel (integer swaps don't earn VMEM residency).
    def body(j, perm):
        p = piv[j]
        pj, pp_ = perm[j], perm[p]
        return perm.at[j].set(pp_).at[p].set(pj)

    perm = lax.fori_loop(0, nbw, body, jnp.arange(M))
    return packed, perm
