"""Fused Pallas ``_potrf_inv``: blocked lower Cholesky of a diagonal
block AND its triangular inverse in one kernel launch.

The XLA path (``lapack.cholesky._potrf_inv_impl``) already restructures
the work into ``bs``-sized diagonal potrfs plus matmul assembly, but it
still pays one ``cholesky`` + one ``triangular_solve`` launch per block
-- latency-bound inner loops on the factorization spine.  Here the
whole (w, w) block lives in VMEM: the per-block potrf is an in-kernel
column recurrence, the per-block inverse is an in-kernel forward
substitution, and the inverse assembly / trailing updates are the same
MXU dots the reference issues -- all inside one ``pallas_call``.

Everything is written in the forms the TPU compiler lowers: the
right-looking factorization runs IN PLACE on the ``L`` output ref
through static, tile-aligned block windows (the block is padded to a
LANE multiple with an identity diagonal, so the padded extent factors
as itself); the ``bs``-sized diagonal recurrences run on scratch refs,
rows through dynamic sublane loads/stores, columns through lane-masked
reductions of the SYMMETRIC running block (row j is column j, so no
(b, 1) -> (1, b) transpose is ever needed).  The math matches the
reference block-for-block but the scalar recurrences round differently
from XLA's native potrf/trsm, so the twin contract is residual-bounded
(``L L^H ~ A``, ``Li L ~ I``), not bit-pinned -- see
``tests/kernels/test_chol_panel.py`` for the documented bounds.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import (LANE, compiler_params, interpret_default,
                     kernel_trace, loop32, round_up)

_HI = lax.Precision.HIGHEST


def _dot_nt(a, b, precision):
    """a @ b^T on the MXU without materializing the transpose."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           precision=precision,
                           preferred_element_type=a.dtype)


def _chol_unb(a_ref):
    """Unblocked lower Cholesky, in place, of the symmetric (b, b) block
    in ``a_ref``: right-looking column recurrence that keeps the running
    Schur complement fully symmetric, so the multiplier ROW comes from a
    dynamic sublane load and the multiplier COLUMN from a lane-masked
    reduction of the same values.  On exit the lower triangle holds L
    (the strict upper triangle is scratch)."""
    b = a_ref.shape[0]
    ci = lax.broadcasted_iota(jnp.int32, (b, b), 1)
    rcol = lax.broadcasted_iota(jnp.int32, (b, 1), 0)
    crow = lax.broadcasted_iota(jnp.int32, (1, b), 1)

    def body(j, carry):
        A = a_ref[...]
        rowj = a_ref[pl.ds(j, 1), :]
        colj = jnp.sum(jnp.where(ci == j, A, 0), axis=1, keepdims=True)
        dj = jnp.sqrt(jnp.sum(jnp.where(crow == j, rowj, 0), axis=1,
                              keepdims=True))
        lrow = jnp.where(crow > j, rowj / dj, 0)
        lcol = jnp.where(rcol > j, colj / dj, 0)
        A = A - lcol * lrow
        a_ref[...] = jnp.where(ci == j, jnp.where(rcol == j, dj, lcol), A)
        return carry

    loop32(0, b, body)


def _trinv_unb(l_ref, x_ref):
    """Forward-substitution inverse of the (b, b) lower-triangular block
    in ``l_ref`` into ``x_ref``: row i of L^{-1} from rows < i, one
    (1, b) x (b, b) dot per step."""
    b = l_ref.shape[0]
    dt = l_ref.dtype
    crow = lax.broadcasted_iota(jnp.int32, (1, b), 1)
    x_ref[...] = jnp.zeros((b, b), dt)

    def body(i, carry):
        lrow = l_ref[pl.ds(i, 1), :]
        dii = jnp.sum(jnp.where(crow == i, lrow, 0), axis=1, keepdims=True)
        lstrict = jnp.where(crow < i, lrow, 0)
        corr = jnp.dot(lstrict, x_ref[...], precision=_HI,
                       preferred_element_type=dt)
        erow = jnp.where(crow == i, jnp.ones((), dt), jnp.zeros((), dt))
        x_ref[pl.ds(i, 1), :] = (erow - corr) / dii
        return carry

    loop32(0, b, body)


def _potrf_inv_kernel(d_ref, l_ref, li_ref, *scratch, bs, precision):
    wp = d_ref.shape[0]
    dt = d_ref.dtype
    # symmetrize from the lower triangle, as the reference does; the
    # running Schur complement lives in l_ref, full-symmetric, and each
    # finished block column overwrites it with L
    d = jnp.tril(d_ref[...])
    l_ref[...] = d + jnp.tril(d, -1).T
    li_ref[...] = jnp.zeros((wp, wp), dt)
    for s in range(0, wp, bs):
        e = min(s + bs, wp)
        # a narrower last block has scratch of its own size (a row loaded
        # at a dynamic sublane offset must span its ref's full width)
        blk, x = scratch[:2] if e - s == bs else scratch[2:]
        blk[...] = l_ref[s:e, s:e]
        _chol_unb(blk)
        Lkk = jnp.tril(blk[...])
        blk[...] = Lkk
        _trinv_unb(blk, x)
        Likk = x[...]
        l_ref[s:e, s:e] = Lkk
        # inverse assembly: Li[s:e, :s] = -Likk @ L[s:e, :s] @ Li[:s, :s]
        if s > 0:
            corr = jnp.dot(
                Likk, jnp.dot(l_ref[s:e, :s], li_ref[:s, :s],
                              precision=precision,
                              preferred_element_type=dt),
                precision=precision, preferred_element_type=dt)
            li_ref[s:e, :s] = -corr
        li_ref[s:e, s:e] = Likk
        if e < wp:
            B21 = _dot_nt(l_ref[e:, s:e], Likk, precision)
            l_ref[e:, s:e] = B21
            l_ref[e:, e:] = l_ref[e:, e:] - _dot_nt(B21, B21, precision)
    l_ref[...] = jnp.tril(l_ref[...])


def potrf_inv(D, precision=None, *, bs: int = 512, interpret=None):
    """Fused twin of ``lapack.cholesky._potrf_inv_impl``: one launch,
    same contract ``(L, L^{-1})`` from a (w, w) Hermitian block (lower
    triangle valid).  Real dtypes only -- complex panels are gated back
    to the XLA path by the ``panel_impl`` dispatch."""
    w = D.shape[0]
    if jnp.issubdtype(D.dtype, jnp.complexfloating):
        raise ValueError("pallas potrf_inv is real-only; the panel_impl "
                         "dispatch falls back to xla for complex dtypes")
    # factor-forming dots run at full accumulation, matching lu._hi
    precision = _HI if precision is None else precision
    # tile-aligned blocks: bs is a LANE multiple and the block is padded
    # to a LANE multiple with an identity diagonal (diag(A, I) factors as
    # diag(L, I), so the pad never touches the real factor)
    wp = round_up(w, LANE)
    bs = min(round_up(bs, LANE), wp)
    Dp = D
    if wp != w:
        Dp = jnp.pad(D, ((0, wp - w), (0, wp - w))) + jnp.diag(
            (jnp.arange(wp) >= w).astype(D.dtype))
    kern = functools.partial(_potrf_inv_kernel, bs=bs, precision=precision)
    shp = jax.ShapeDtypeStruct((wp, wp), D.dtype)
    scratch = [pltpu.VMEM((b, b), D.dtype)
               for b in (bs, wp % bs) if b for _ in range(2)]
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    interpret = interpret_default(interpret)
    with kernel_trace(interpret):
        L, Li = pl.pallas_call(
            kern,
            out_shape=(shp, shp),
            in_specs=[vmem],
            out_specs=(vmem, vmem),
            scratch_shapes=scratch,
            compiler_params=compiler_params(),
            interpret=interpret,
            name="el_potrf_inv_panel",
        )(Dp)
    return L[:w, :w], Li[:w, :w]
