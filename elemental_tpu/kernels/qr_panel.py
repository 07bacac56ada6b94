"""Fused Pallas QR panel: the larfg reflector chain AND the larft
T-triangle build in one kernel launch.

The XLA path runs ``lapack.qr._panel_qr`` (per column: a norm, a
divide, one (1, n) row dot, one rank-1 update) and then ``_larft`` (a
Gram matmul plus k small matvecs) as separate fori_loops -- dozens of
latency-bound launches per panel on the factorization spine.  Here the
panel is VMEM-resident: the reflector chain, the Gram product
``V^H V``, and the forward-columnwise T recurrence all run inside one
``pallas_call``, returning ``(packed V\\R, tau, T)`` so the driver
skips the separate ``_larft`` call entirely.

The kernel body mirrors the reference step-for-step (same degenerate
guards) in the forms the TPU compiler lowers: it works IN PLACE on the
output ref; a column is read by a lane-masked reduction and written back
by a lane-masked select; scalars stay (1, 1)-shaped; the reflector's row
product v^H P is a sublane reduction on the VPU (exact float32, where
the reference asks the MXU for HIGHEST); and the T recurrence runs on
T^T, row by row through dynamic sublane loads/stores (B = V^H V is
symmetric, so row i of B is its column i), with one aligned transpose at
the end.  Reductions group differently than the XLA (M,)-vector sums, so
the twin contract is residual-bounded (``Q R ~ A``, orthonormal Q), not
bit-pinned -- see ``tests/kernels/test_qr_panel.py`` for the documented
bounds.  Real dtypes only; complex panels are gated back to XLA by the
``panel_impl`` dispatch.
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

_HI = lax.Precision.HIGHEST


def _qr_panel_kernel(p_ref, out_ref, tau_ref, t_ref, b_ref, tt_ref, *, k):
    mp, wp = out_ref.shape
    dt = out_ref.dtype
    out_ref[...] = p_ref[...]
    tau_ref[...] = jnp.zeros((1, wp), dt)
    ridx = lax.broadcasted_iota(jnp.int32, (mp, 1), 0)
    cidx = lax.broadcasted_iota(jnp.int32, (1, wp), 1)
    one, zero = jnp.ones((), dt), jnp.zeros((), dt)

    def body(j, carry):
        # the larfg recurrence of _panel_qr, column-masked: padded rows
        # are zero and contribute exact zeros to sigma / the row product
        P = out_ref[...]
        col = jnp.sum(jnp.where(cidx == j, P, 0), axis=1, keepdims=True)
        alpha = jnp.sum(jnp.where(ridx == j, col, 0), axis=0, keepdims=True)
        tail = jnp.where(ridx > j, col, 0)
        sigma = jnp.sum(tail * tail, axis=0, keepdims=True)
        anorm = jnp.sqrt(alpha * alpha + sigma)
        beta = -jnp.sign(jnp.where(alpha == 0, one, alpha)) * anorm
        degenerate = anorm == 0
        safe_beta = jnp.where(degenerate, one, beta)
        tau_j = jnp.where(degenerate, zero, (safe_beta - alpha) / safe_beta)
        denom = alpha - safe_beta
        safe_denom = jnp.where(denom == 0, one, denom)
        v = jnp.where(ridx > j, col / safe_denom, 0)
        v = jnp.where(ridx == j, jnp.where(degenerate, zero, one), v)
        w = jnp.sum(v * P, axis=0, keepdims=True)
        P = P - jnp.where(cidx > j, (tau_j * v) * w, 0)
        newcol = jnp.where(ridx > j, v, col)
        newcol = jnp.where(ridx == j, beta, newcol)
        out_ref[...] = jnp.where(cidx == j, newcol, P)
        tau_ref[...] = jnp.where(cidx == j, tau_j, tau_ref[...])
        return carry

    loop32(0, k, body)

    # larft, fused: V from the packed panel, one Gram dot, then the
    # forward-columnwise T recurrence of _larft, run on T^T so each step
    # is one row: T^T[i, :] = -tau_i * (B[i, :i] @ T^T), T^T[i, i] =
    # tau_i.  Padded V columns are unit vectors e_j but every read is
    # masked to < i < k, so the padded border of T stays exactly zero.
    P = out_ref[...]
    V = jnp.where(ridx > cidx, P, 0) + jnp.where(ridx == cidx, one, zero)
    b_ref[...] = lax.dot_general(V, V, (((0,), (0,)), ((), ())),
                                 precision=_HI, preferred_element_type=dt)
    tt_ref[...] = jnp.zeros((wp, wp), dt)
    tau = tau_ref[...]

    def tbody(i, carry):
        bi = jnp.where(cidx < i, b_ref[pl.ds(i, 1), :], 0)
        taui = jnp.sum(jnp.where(cidx == i, tau, 0), axis=1, keepdims=True)
        row = -taui * jnp.dot(bi, tt_ref[...], precision=_HI,
                              preferred_element_type=dt)
        tt_ref[pl.ds(i, 1), :] = jnp.where(cidx == i, taui, row)
        return carry

    loop32(0, k, tbody)
    t_ref[...] = tt_ref[...].T


def qr_panel(P, *, interpret=None):
    """Fused twin of ``lapack.qr._panel_qr`` + ``_larft``: one launch
    returning ``(packed V\\R, tau, T)`` with the same LAPACK larfg
    conventions (real beta, H_j = I - tau_j v_j v_j^H applied as H^H)."""
    M, k = P.shape
    if jnp.issubdtype(P.dtype, jnp.complexfloating):
        raise ValueError("pallas QR panel is real-only; the panel_impl "
                         "dispatch falls back to xla for complex dtypes")
    Pp = pad_tiles(P)
    mp, wp = Pp.shape
    kern = functools.partial(_qr_panel_kernel, k=k)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    sq = pltpu.VMEM((wp, wp), P.dtype)
    interpret = interpret_default(interpret)
    with kernel_trace(interpret):
        packed, tau, T = pl.pallas_call(
            kern,
            out_shape=(jax.ShapeDtypeStruct((mp, wp), P.dtype),
                       jax.ShapeDtypeStruct((1, wp), P.dtype),
                       jax.ShapeDtypeStruct((wp, wp), P.dtype)),
            in_specs=[vmem],
            out_specs=(vmem, vmem, vmem),
            scratch_shapes=[sq, sq],
            compiler_params=compiler_params(),
            interpret=interpret,
            name="el_qr_panel",
        )(Pp)
    return packed[:M, :k], tau[0, :k], T[:k, :k]
