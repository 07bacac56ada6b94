"""Level-3 BLAS: SUMMA Gemm, Herk/Syrk, Trrk, blocked Trsm.

Reference: Elemental ``src/blas_like/level3/`` -- ``Gemm.cpp`` +
``Gemm/{NN,NT,TN,TT}.hpp`` (SUMMA stationary-A/B/C variant selection),
``Herk``/``Syrk`` over ``Trrk``, ``Trsm.cpp`` + ``Trsm/*.hpp`` (blocked
panel solves).

TPU-native design: the stacked-storage array of a DistMatrix is a
row/column PERMUTATION of the global matrix (P_S A Q_S' for the cyclic
permutations of the dim strides).  Therefore, whenever two operands agree
on the contraction dimension's stride, their storage arrays multiply
directly -- ``P A Q^T  @  Q B R^T = P (A B) R^T`` -- and GSPMD lowers the
sharded matmul to local MXU calls plus the right ICI collective
(replicated-k: pure local; k sharded on a mesh axis: local + psum over
that axis).  So SUMMA here is: redistribute panels with the engine, then a
plain ``jnp.matmul`` on storage, letting XLA insert the collectives --
the scaling-book recipe, which is exactly what the reference hand-codes
with MPI AllGather + local BLAS + ReduceScatter.

Panel loops are Python-unrolled (static shapes per iteration; jit traces
once per (shape, grid)).
"""
from __future__ import annotations

import math

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..core.dist import MC, MR, VC, VR, STAR
from ..core.distmatrix import DistMatrix, zeros as dm_zeros
from ..core.view import view, update_view
from ..obs import metrics as _metrics
from ..obs.tracer import (NULL_HOOK as _NULL_HOOK, phase_hook as _phase_hook,
                          scoped as _scoped)
from ..redist.engine import redistribute, transpose_dist, panel_spread
from .level1 import _global_indices


def _check_mcmr(*Ms: DistMatrix):
    g = Ms[0].grid
    for A in Ms:
        if A.dist != (MC, MR) or (A.calign, A.ralign) != (0, 0):
            raise ValueError(f"expected zero-aligned [MC,MR] operand, got {A}")
        if A.grid != g:
            raise ValueError("operands on different grids")


# The canonical blocksize rule lives in the tune subsystem (ISSUE 4);
# re-exported here under its historical name for the lapack drivers that
# import it from this module.
from ..tune.policy import blocksize_policy as _blocksize  # noqa: E402


def _resolve_auto(op: str, gshape, dtype, grid, **knobs) -> dict:
    """Route any ``'auto'`` knob through the tuner (cache > cost model);
    explicit values pass through untouched."""
    from ..tune.policy import resolve_knobs
    return resolve_knobs(op, gshape=gshape, dtype=dtype, grid=grid,
                         knobs=knobs)


def _orient(A: DistMatrix, orient: str) -> DistMatrix:
    """Materialize op(A) as a zero-aligned [MC,MR] matrix.

    The engine's transpose-exchange chain ([MR,MC] -> [MC,MR]) makes this a
    handful of fast hops (the reference's ``Transpose`` op does the same via
    ``copy::TransposeDist`` + ``Copy``).
    """
    if orient == "N":
        return A
    return redistribute(transpose_dist(A, conj=(orient == "C")), MC, MR)


def _mask_triangle(C: DistMatrix, uplo: str, strict: bool = False):
    """Boolean mask over C's storage selecting the given global triangle."""
    I, J = _global_indices(C)
    if uplo.upper().startswith("L"):
        return (J[None, :] < I[:, None]) if strict else (J[None, :] <= I[:, None])
    return (J[None, :] > I[:, None]) if strict else (J[None, :] >= I[:, None])


# ---------------------------------------------------------------------
# Gemm (SUMMA)
# ---------------------------------------------------------------------

@_scoped("el.gemm")
def gemm(A: DistMatrix, B: DistMatrix, alpha=1.0, beta=0.0, C: DistMatrix | None = None,
         orient_a: str = "N", orient_b: str = "N", alg: str = "auto",
         nb: int | str | None = None, precision=None,
         comm_precision: str | None = None,
         redist_path: str | None = None) -> DistMatrix:
    """C := alpha op(A) op(B) + beta C on [MC,MR] (SUMMA).

    ``alg``: 'auto' routes through the tuning subsystem (measured-cache
    winner first, else the closed-form ring-model cost comparison of the
    SUMMA schedules -- the principled version of the reference's
    largest-operand-stationary heuristic in ``Gemm.cpp``), or one of
    'A' / 'B' / 'C' / 'dot' / 'gspmd' / 'slice' explicitly ('gspmd' =
    single storage matmul, XLA chooses the schedule; 'slice' = the
    one-sided slicing schedule of :func:`_summa_slice` -- three hops of
    one collective each through the engine's fused kernels (an
    all-gather and two all-to-alls over one mesh axis, unpacked in whole
    blocks), no ring, the tall-skinny/rectangular winner).
    ``nb='auto'`` likewise asks the tuner for the panel width; an
    explicit value always wins ('dot', 'gspmd' and 'slice' ignore it).
    On a grid of more than one device the resolved ``alg`` ticks the
    trace-time counter ``gemm_route{alg}``.

    ``comm_precision`` (``None`` | ``'bf16'`` | ``'int8'`` | ``'auto'``)
    selects the wire precision of the SUMMA panel moves (the per-panel
    operand redistributions; GSPMD-inserted contraction psums stay full
    precision): narrow encode -> collective -> decode, 2x fewer bytes on
    the wire.  Opt-in; ``None`` (default) is bit-identical.

    ``redist_path`` (``None`` | ``'chain'`` | ``'direct'`` | ``'auto'``,
    ISSUE 12) selects the route of the per-panel operand redistributions:
    ``'direct'`` replaces the factored multi-hop chains with the one-shot
    compiled plan (``redist.plan``), ``'auto'`` asks the tuner (knob) and
    falls back to the per-call ring-model arbitration.  ``None`` (default)
    keeps the bit-identical chained engine.

    Tiled ``BlockMatrix`` operands are accepted via read-proxy conversion
    (``DistMatrixReadProxy``): they re-lay out to [MC,MR] on entry; the
    result converts back to tiled when every input was tiled.
    """
    from ..core.block import BlockMatrix, as_elemental, block_from_cyclic
    tiled_in = [isinstance(x, BlockMatrix) for x in (A, B, C)
                if x is not None]
    ret_tiled = bool(tiled_in) and all(tiled_in)
    A, B = as_elemental(A), as_elemental(B)
    if C is not None:
        C = as_elemental(C)
    if ret_tiled:
        out = gemm(A, B, alpha, beta, C, orient_a, orient_b, alg, nb,
                   precision, comm_precision, redist_path)
        return block_from_cyclic(out)
    A = _orient(A, orient_a)
    B = _orient(B, orient_b)
    _check_mcmr(A, B)
    m, k = A.gshape
    k2, n = B.gshape
    if k != k2:
        raise ValueError(f"inner dims mismatch: {A.gshape} x {B.gshape}")
    if C is None:
        dts = [A.dtype, B.dtype]
        if isinstance(alpha, complex) or isinstance(beta, complex):
            dts.append(jnp.complex64)
        C = dm_zeros(m, n, MC, MR, A.grid, dtype=jnp.result_type(*dts))
        beta = 0.0
    else:
        _check_mcmr(A, B, C)
        if C.gshape != (m, n):
            raise ValueError(f"C shape {C.gshape} != ({m},{n})")

    if alg == "auto" or isinstance(nb, str) or comm_precision == "auto" \
            or redist_path == "auto":
        kn = _resolve_auto("gemm", (m, k, n), C.dtype, A.grid,
                           alg=alg, nb=nb, comm_precision=comm_precision,
                           redist_path=redist_path)
        alg, nb, comm_precision = kn["alg"], kn["nb"], kn["comm_precision"]
        redist_path = kn.get("redist_path")
    from ..redist.quantize import check_comm_precision
    check_comm_precision(comm_precision)
    cp, rp = comm_precision, redist_path
    if A.grid.size > 1:
        _metrics.inc("gemm_route", alg=alg)
    tm = _phase_hook("gemm", alg=alg)
    tm.start()
    if alg == "C":
        return _summa_c(alpha, A, B, beta, C, nb, precision, tm, cp, rp)
    if alg == "A":
        return _summa_a(alpha, A, B, beta, C, nb, precision, tm, cp, rp)
    if alg == "B":
        return _summa_b(alpha, A, B, beta, C, nb, precision, tm, cp, rp)
    if alg == "dot":
        return _summa_dot(alpha, A, B, beta, C, precision, tm, cp, rp)
    if alg == "slice":
        return _summa_slice(alpha, A, B, beta, C, precision, tm, cp)
    if alg == "gspmd":
        # one-shot: re-land B's k-rows on A's k-col cyclic order ([MR,STAR]),
        # then a single storage matmul -- GSPMD inserts the psum over mr.
        with tm.phase("panel", 0) as ph:
            Bk = redistribute(B, MR, STAR, comm_precision=cp)
            d = jnp.matmul(A.local, Bk.local, precision=precision)
            D = DistMatrix(d, (m, n), MC, STAR, 0, 0, A.grid)
            out = redistribute(D, MC, MR)
            res = C.with_local(_safe_astype(
                alpha * out.local + (beta * C.local if _nonzero(beta) else 0),
                C.dtype))
            ph.done(res.local)
        return res
    raise ValueError(f"unknown gemm alg {alg!r}")


def _summa_c(alpha, A, B, beta, C, nb, precision, tm=_NULL_HOOK, cp=None,
             rp=None):
    """Stationary-C (``gemm::SUMMA_NNC``): per k-panel, A1 -> [MC,STAR]
    (AllGather over mr), B1 -> [STAR,MR] (AllGather over mc), local MXU
    product accumulates into C's storage."""
    m, k = A.gshape
    n = B.gshape[1]
    r, c = A.grid.height, A.grid.width
    kb = _blocksize(nb, math.lcm(r, c), k)
    acc = beta * C.local if _nonzero(beta) else jnp.zeros_like(C.local)
    for i, s in enumerate(range(0, k, kb)):
        e = min(s + kb, k)
        with tm.phase("panel", i) as ph:
            A1 = redistribute(view(A, cols=(s, e)), MC, STAR,
                              comm_precision=cp, path=rp)
            B1 = redistribute(view(B, rows=(s, e)), STAR, MR,
                              comm_precision=cp, path=rp)
            acc = acc + alpha * jnp.matmul(A1.local, B1.local,
                                           precision=precision)
            ph.done(acc)
    return C.with_local(_safe_astype(acc, C.dtype))


def _summa_a(alpha, A, B, beta, C, nb, precision, tm=_NULL_HOOK, cp=None,
             rp=None):
    """Stationary-A (``gemm::SUMMA_NNA``): per C column panel, B1 ->
    [MR,STAR]; the k-contraction is sharded over mr on both operands, so the
    storage matmul lowers to local product + psum over mr -> [MC,STAR]
    partial panel, filtered onto [MC,MR]."""
    m, k = A.gshape
    n = B.gshape[1]
    r, c = A.grid.height, A.grid.width
    jb = _blocksize(nb, c, n)
    out = C.with_local(_safe_astype(beta * C.local, C.dtype)
                       if _nonzero(beta) else jnp.zeros_like(C.local))
    for i, s in enumerate(range(0, n, jb)):
        e = min(s + jb, n)
        with tm.phase("panel", i) as ph:
            B1 = redistribute(view(B, cols=(s, e)), MR, STAR,
                              comm_precision=cp, path=rp)
            d = jnp.matmul(A.local, B1.local, precision=precision)   # [MC,STAR] storage
            D1 = DistMatrix(d, (m, e - s), MC, STAR, 0, 0, A.grid)
            panel = redistribute(D1, MC, MR)
            cur = view(out, cols=(s, e))
            out = update_view(out, cur.with_local(cur.local + _safe_astype(alpha * panel.local, C.dtype)),
                              cols=(s, e))
            ph.done(out.local)
    return out


def _summa_b(alpha, A, B, beta, C, nb, precision, tm=_NULL_HOOK, cp=None,
             rp=None):
    """Stationary-B: per C row panel, A1^T -> [MC,STAR] (so the k-contraction
    is sharded over mc on both operands); local product + psum over mc ->
    [STAR,MR] partial panel, filtered onto [MC,MR]."""
    m, k = A.gshape
    n = B.gshape[1]
    r, c = A.grid.height, A.grid.width
    ib = _blocksize(nb, r, m)
    out = C.with_local(_safe_astype(beta * C.local, C.dtype)
                       if _nonzero(beta) else jnp.zeros_like(C.local))
    for i, s in enumerate(range(0, m, ib)):
        e = min(s + ib, m)
        with tm.phase("panel", i) as ph:
            A1T = redistribute(transpose_dist(view(A, rows=(s, e))), MC, STAR,
                               comm_precision=cp, path=rp)
            d = jnp.matmul(A1T.local.T, B.local, precision=precision)  # [STAR,MR] storage
            D1 = DistMatrix(d, (e - s, n), STAR, MR, 0, 0, A.grid)
            panel = redistribute(D1, MC, MR)
            cur = view(out, rows=(s, e))
            out = update_view(out, cur.with_local(cur.local + _safe_astype(alpha * panel.local, C.dtype)),
                              rows=(s, e))
            ph.done(out.local)
    return out


def _summa_dot(alpha, A, B, beta, C, precision, tm=_NULL_HOOK, cp=None,
               rp=None):
    """SUMMA-Dot (``gemm::SUMMA_NNDot``, the small-C case): shard the
    inner dimension 1-D cyclic on BOTH operands ([STAR,VC] x [VC,STAR] --
    the same cyclic permutation on each side, so the storage matmul
    contracts correctly), local (m, k/p) x (k/p, n) products, one psum
    over all devices into the replicated C, filter onto [MC,MR].

    On a 1x1 grid the storage arrays ARE the global operands, so the
    [STAR,VC] round-trip is pure dispatch overhead: early-out to one local
    matmul.  ``beta`` may be any scalar (incl. complex); a complex result
    landing in a real C still raises through :func:`_safe_astype`."""
    m, n = C.gshape
    with tm.phase("panel", 0) as ph:
        if A.grid.size == 1:
            d = jnp.matmul(A.local, B.local, precision=precision)
        else:
            Avc = redistribute(A, STAR, VC, comm_precision=cp, path=rp)
            Bvc = redistribute(B, VC, STAR, comm_precision=cp, path=rp)
            dl = jnp.matmul(Avc.local, Bvc.local, precision=precision)
            D = DistMatrix(dl, (m, n), STAR, STAR, 0, 0, A.grid)
            d = redistribute(D, MC, MR).local
        res = C.with_local(_safe_astype(
            alpha * d + (beta * C.local if _nonzero(beta) else 0),
            C.dtype))
        ph.done(res.local)
    return res


def _summa_slice(alpha, A, B, beta, C, precision, tm=_NULL_HOOK, cp=None):
    """Slicing-based one-sided gemm (``alg='slice'``, the arXiv 2510.08874
    direction): every device owns one contiguous-cyclic SLICE of C's rows
    (or columns) and gathers, in ONE collective per operand, exactly the
    A rows (B columns) that slice needs plus the shared small operand --
    no k-panel ring, no per-panel barrier.

    Row mode (``m >= n`` or an Nx1 grid): A -> [VC,STAR] (each device
    takes its 1-D cyclic row slice -- one all-to-all over mr), B ->
    [STAR,STAR] (the small operand, one all-gather over the whole grid),
    then a fully LOCAL contraction (k is unsharded on both sides, so no
    hidden psum) lands D = A_slice @ B as [VC,STAR] storage, filtered
    back onto [MC,MR] by a third hop (one all-to-all over mr).  Column
    mode mirrors with [STAR,STAR] x [STAR,VR] over mc.  Degeneracies:
    1x1 grids early-out to one local matmul with ZERO redistributes
    (pinned); on Nx1 (row mode) and 1xN (column mode) grids two of the
    three hops are pure local filters, leaving a single collective.

    The three hops take the engine's default route, which for each of
    the six pairs is a fused single-collective kernel of
    ``redist.engine._fused_dispatch`` unpacked by whole-block
    interleaves (``redist_unpack{impl,dim}``); never ``path='direct'``,
    whose dense index tables run on a TPU as a gather and a scatter of
    one entry at a time.  ``comm_precision`` passes through to every
    operand hop: ``'bf16'`` casts each payload, ``'int8'`` block-scales
    the [STAR,STAR] leg and degrades to ``'bf16'`` on the [V] leg, as
    the engine documents.  The route takes no ``redist_path``.  The
    fused hops ship the wire bytes of the compiled plan of the same
    pair, to the byte, so the tuner prices them with the same
    ``compile_plan`` byte math (``tune.cost_model``), which is what
    makes ``alg='auto'`` pick 'slice' on tall-skinny / non-square-grid
    geometry and keep the SUMMA twins elsewhere."""
    m, n = C.gshape
    g = A.grid
    with tm.phase("panel", 0) as ph:
        if g.size == 1:
            d = jnp.matmul(A.local, B.local, precision=precision)
        else:
            from ..redist.plan import slice_row_mode
            if slice_row_mode(m, n, (g.height, g.width)):
                As = redistribute(A, VC, STAR, comm_precision=cp)
                Bs = redistribute(B, STAR, STAR, comm_precision=cp)
                dl = jnp.matmul(As.local, Bs.local, precision=precision)
                D = DistMatrix(dl, (m, n), VC, STAR, 0, 0, g)
            else:
                As = redistribute(A, STAR, STAR, comm_precision=cp)
                Bs = redistribute(B, STAR, VR, comm_precision=cp)
                dl = jnp.matmul(As.local, Bs.local, precision=precision)
                D = DistMatrix(dl, (m, n), STAR, VR, 0, 0, g)
            d = redistribute(D, MC, MR).local
        res = C.with_local(_safe_astype(
            alpha * d + (beta * C.local if _nonzero(beta) else 0),
            C.dtype))
        ph.done(res.local)
    return res


def _nonzero(x) -> bool:
    # complex(0) counts as zero: a 0j beta must not force a complex
    # accumulator (and a TypeError out of _safe_astype) onto a real C
    return not (isinstance(x, (int, float, complex)) and x == 0)


def _safe_astype(x, dtype):
    """astype that refuses to silently drop an imaginary part."""
    if jnp.iscomplexobj(x) and not jnp.issubdtype(dtype, jnp.complexfloating):
        raise TypeError(f"complex result cannot be stored in {dtype} output; "
                        "pass a complex C (or complex operands)")
    return x.astype(dtype)


# ---------------------------------------------------------------------
# Trrk / Herk / Syrk
# ---------------------------------------------------------------------

def trrk(uplo: str, alpha, A_mc: DistMatrix, B_mr: DistMatrix, beta, C: DistMatrix,
         precision=None) -> DistMatrix:
    """Triangular rank-k: C(tri) := alpha A B + beta C(tri), other triangle
    untouched.  A is [MC,STAR], B is [STAR,MR] (the reference's
    ``LocalTrrk``, the factorization trailing-update workhorse).

    TPU note: this computes the full local product and masks it.  The
    masked half is NOT free: the compiler multiplies both triangles, and on
    the chip the grid Cholesky's updates in this form ran at the matmuls'
    roofline with half the flops thrown away (``cholesky/update`` 1.51 s of
    a 2.00 s solve at N = 65536 on 2x2; PERF.md 6, PR 36).  The Cholesky
    drivers walk stripes of the lower trapezoid instead
    (``lapack/cholesky.py``); a caller with a large C should too.
    """
    if A_mc.dist != (MC, STAR) or B_mr.dist != (STAR, MR):
        raise ValueError("trrk expects A [MC,STAR], B [STAR,MR]")
    _check_mcmr(C)
    mask = _mask_triangle(C, uplo)
    full = jnp.matmul(A_mc.local, B_mr.local, precision=precision)
    tri_new = alpha * full + beta * C.local
    return C.with_local(jnp.where(mask, _safe_astype(tri_new, C.dtype), C.local))


@_scoped("el.herk")
def herk(uplo: str, A: DistMatrix, alpha=1.0, beta=0.0, C: DistMatrix | None = None,
         orient: str = "N", nb: int | str | None = None, precision=None,
         conj: bool = True, comm_precision: str | None = None,
         redist_path: str | None = None) -> DistMatrix:
    """C(tri) := alpha op(A) op(A)^H + beta C(tri)  (orient 'N' or 'C'/'T').

    Per k-panel: A1 -> [VC,STAR], then the fused engine ``panel_spread``
    produces the [MC,STAR] panel and its [STAR,MR] adjoint in ONE
    collective round (the Cholesky trailing-update chain, cf.
    ``cholesky::LVar3``); masked local update.  ``nb='auto'`` asks the
    tuning subsystem for the k-panel width.  ``comm_precision`` selects
    the wire precision of the panel move + spread (see :func:`gemm`).
    ``redist_path='direct'`` replaces the [VC,STAR] hop + spread (two
    rounds per panel) with one one-shot [MC,MR] -> [STAR,STAR] exchange
    followed by zero-round local filters.
    """
    if orient != "N":
        A = _orient(A, "C" if conj else "T")
    _check_mcmr(A)
    m, k = A.gshape
    if isinstance(nb, str) or comm_precision == "auto" or redist_path == "auto":
        kn = _resolve_auto("herk", (m, k), A.dtype, A.grid, nb=nb,
                           comm_precision=comm_precision,
                           redist_path=redist_path)
        nb, comm_precision = kn["nb"], kn["comm_precision"]
        redist_path = kn.get("redist_path")
    from ..redist.quantize import check_comm_precision
    check_comm_precision(comm_precision)
    r, c = A.grid.height, A.grid.width
    if C is None:
        C = dm_zeros(m, m, MC, MR, A.grid, dtype=A.dtype)
        beta = 0.0
    else:
        _check_mcmr(A, C)
        if C.gshape != (m, m):
            raise ValueError(f"C shape {C.gshape} != ({m},{m})")
    tm = _phase_hook("herk")
    tm.start()
    kb = _blocksize(nb, c, k)
    mask = _mask_triangle(C, uplo)
    acc = beta * C.local if _nonzero(beta) else jnp.zeros_like(C.local)
    for i, s in enumerate(range(0, k, kb)):
        e = min(s + kb, k)
        with tm.phase("spread", i) as ph:
            if redist_path == "direct":
                # One one-shot exchange per panel; the [MC,STAR] panel and
                # its [STAR,MR] adjoint are then zero-round local filters.
                A1_ss = redistribute(view(A, cols=(s, e)), STAR, STAR,
                                     comm_precision=comm_precision,
                                     path="direct")
                A1_mc = redistribute(A1_ss, MC, STAR)
                A1H_mr = redistribute(transpose_dist(A1_ss, conj=conj),
                                      STAR, MR)
            else:
                A1_vc = redistribute(view(A, cols=(s, e)), VC, STAR,
                                     comm_precision=comm_precision,
                                     path=redist_path)
                A1_mc, A1H_mr = panel_spread(A1_vc, conj=conj,
                                             comm_precision=comm_precision)
            ph.done(A1_mc.local, A1H_mr.local)
        with tm.phase("update", i) as ph:
            acc = acc + alpha * jnp.matmul(A1_mc.local, A1H_mr.local,
                                           precision=precision)
            ph.done(acc)
    return C.with_local(jnp.where(mask, _safe_astype(acc, C.dtype), C.local))


def syrk(uplo: str, A: DistMatrix, alpha=1.0, beta=0.0, C: DistMatrix | None = None,
         orient: str = "N", nb: int | None = None, precision=None) -> DistMatrix:
    return herk(uplo, A, alpha, beta, C, orient=orient, nb=nb,
                precision=precision, conj=False)


# ---------------------------------------------------------------------
# Trsm (blocked panel solves)
# ---------------------------------------------------------------------

@_scoped("el.trsm")
def trsm(side: str, uplo: str, orient: str, A: DistMatrix, B: DistMatrix,
         alpha=1.0, unit: bool = False, nb: int | str | None = None,
         precision=None, comm_precision: str | None = None,
         redist_path: str | None = None) -> DistMatrix:
    """Solve op(A) X = alpha B (side 'L') or X op(A) = alpha B (side 'R');
    A triangular [MC,MR].  Reference: ``El::Trsm`` 8 side/uplo/orientation
    cases (``src/blas_like/level3/Trsm/*.hpp``).

    ``nb='auto'`` asks the tuning subsystem for the panel width (explicit
    values always win).  Right-side solves reduce to left solves of the
    transposed system (X op(A) = B  <=>  op(A)^T X^T = B^T).
    ``comm_precision`` selects the wire precision of the panel moves
    (diagonal-block gathers, RHS panel transport, off-diagonal operand
    moves; see :func:`gemm`).  ``redist_path`` routes those moves through
    the one-shot plan compiler ('direct'), the hop chain ('chain'/None),
    or measured-constant arbitration ('auto'); right-side solves benefit
    most (the entry/exit transposes collapse from 3-hop chains to one
    exchange each)."""
    if isinstance(nb, str) or comm_precision == "auto" or redist_path == "auto":
        kn = _resolve_auto("trsm", B.gshape, B.dtype, B.grid, nb=nb,
                           comm_precision=comm_precision,
                           redist_path=redist_path)
        nb, comm_precision = kn["nb"], kn["comm_precision"]
        redist_path = kn.get("redist_path")
    from ..redist.quantize import check_comm_precision
    check_comm_precision(comm_precision)
    tm = _phase_hook("trsm")
    tm.start()
    trans = orient in ("T", "C")
    conj = orient == "C"
    if side.upper().startswith("R"):
        BT = redistribute(transpose_dist(B), MC, MR, path=redist_path)
        # op(A)^T: N -> T; T -> N; C -> conj-only (trans=False, conj=True)
        XT = _trsm_left(uplo, not trans, conj, A, BT, alpha, unit, nb,
                        precision, tm, comm_precision, redist_path)
        return redistribute(transpose_dist(XT), MC, MR, path=redist_path)
    return _trsm_left(uplo, trans, conj, A, B, alpha, unit, nb, precision,
                      tm, comm_precision, redist_path)


def _trsm_left(uplo: str, trans: bool, conj: bool, A: DistMatrix, B: DistMatrix,
               alpha, unit: bool, nb: int | None, precision,
               tm=_NULL_HOOK, cp=None, rp=None) -> DistMatrix:
    """All eight left cases.  Effective triangle: uplo XOR trans decides the
    sweep direction; per panel the diagonal block is replicated
    ([STAR,STAR]), the RHS panel goes 1-D cyclic ([STAR,VR]) for the local
    triangular solve, and the off-diagonal product rides
    [MC,STAR] x [STAR,MR] storage (pure local)."""
    _check_mcmr(A, B)
    m, n = B.gshape
    if A.gshape != (m, m):
        raise ValueError(f"A {A.gshape} incompatible with B {B.gshape}")
    lower = uplo.upper().startswith("L")
    r, c = A.grid.height, A.grid.width
    ib = _blocksize(nb, math.lcm(r, c), m)
    X = B.with_local(alpha * B.local if _nonzero(alpha - 1) else B.local)
    starts = list(range(0, m, ib))
    forward = lower != trans        # effective-lower => forward sweep
    if not forward:
        starts = starts[::-1]
    for k, s in enumerate(starts):
        e = min(s + ib, m)
        with tm.phase("solve", k) as ph:
            A11 = redistribute(view(A, rows=(s, e), cols=(s, e)), STAR, STAR,
                               comm_precision=cp, path=rp)
            # mask to the stored triangle so opposite-triangle garbage (e.g.
            # the packed L\U format of lu()) can never leak into the solve
            a11 = jnp.tril(A11.local) if lower else jnp.triu(A11.local)
            B1 = redistribute(view(X, rows=(s, e)), STAR, VR,
                              comm_precision=cp, path=rp)
            x1 = lax.linalg.triangular_solve(
                a11, B1.local, left_side=True, lower=lower,
                transpose_a=trans, conjugate_a=conj, unit_diagonal=unit)
            X1 = DistMatrix(x1, B1.gshape, STAR, VR, 0, 0, A.grid)
            X1_mr = redistribute(X1, STAR, MR, comm_precision=cp, path=rp)
            X = update_view(X, redistribute(X1_mr, MC, MR), rows=(s, e))  # local filter
            ph.done(X.local)
        # trailing update of the not-yet-solved rows
        lo, hi = (e, m) if forward else (0, s)
        if lo >= hi:
            continue
        with tm.phase("update", k) as ph:
            if trans:
                # T21 = op(A)[hi-part, s:e] = op(A[s:e, hi-part])
                A1p = redistribute(view(A, rows=(s, e), cols=(lo, hi)), STAR,
                                   MC, comm_precision=cp, path=rp)
                a_loc = A1p.local.T            # [MC,STAR]-storage of A1p^T
            else:
                A1p = redistribute(view(A, rows=(lo, hi), cols=(s, e)), MC,
                                   STAR, comm_precision=cp, path=rp)
                a_loc = A1p.local
            if conj:
                a_loc = jnp.conj(a_loc)
            X = local_rank_update(X, a_loc, X1_mr.local, rows=(lo, hi),
                                  precision=precision)
            ph.done(X.local)
    return X


def local_rank_update(C: DistMatrix, A_loc, B_loc, rows=None, cols=None,
                      alpha=-1.0, precision=None) -> DistMatrix:
    """C[rows, cols] += alpha * A_loc @ B_loc on storage, pure-local.

    ``A_loc`` / ``B_loc`` are the STORAGE arrays of conforming [MC,STAR]
    and [STAR,MR] operands (rows/cols of the product land exactly on the
    view's cyclic layout), so the whole rank-k update is one local MXU
    matmul + writeback -- the reference's ``LocalGemm`` trailing-update
    idiom shared by trsm, quasi_trsm and the LU/look-ahead drivers."""
    sub = view(C, rows=rows, cols=cols)
    upd = jnp.matmul(A_loc, B_loc, precision=precision)
    new = sub.local + (alpha * upd).astype(C.dtype)
    return update_view(C, sub.with_local(new), rows=rows, cols=cols)


def quasi_trsm(side: str, orient: str, A: DistMatrix, B: DistMatrix,
               alpha=1.0, nb: int | None = None, precision=None
               ) -> DistMatrix:
    """Solve op(T) X = alpha B (side 'L') or X op(T) = alpha B (side 'R')
    with T UPPER QUASI-TRIANGULAR (real Schur form: 1x1/2x2 diagonal
    blocks, i.e. an upper triangle plus isolated subdiagonal entries).
    Reference: ``El::QuasiTrsm`` (``src/blas_like/level3/QuasiTrsm/``).

    TPU shape: ONE host read of T's subdiagonal places the panel splits
    so no 2x2 block is cut; each replicated diagonal block then solves
    with a small general ``jnp.linalg.solve`` (quasi-triangular blocks
    are not XLA-triangular-solvable), and the off-panel updates are the
    standard trsm SUMMA products -- the strictly-lower region outside the
    bumps is zero, so the update blocks are genuinely triangular."""
    trans = orient in ("T", "C")
    conj = orient == "C"
    if side.upper().startswith("R"):
        BT = redistribute(transpose_dist(B), MC, MR)
        XT = _quasi_trsm_left(not trans, conj, A, BT, alpha, nb, precision)
        return redistribute(transpose_dist(XT), MC, MR)
    return _quasi_trsm_left(trans, conj, A, B, alpha, nb, precision)


def _quasi_trsm_left(trans: bool, conj: bool, A: DistMatrix, B: DistMatrix,
                     alpha, nb: int | None, precision) -> DistMatrix:
    from ..blas.level1 import get_diagonal
    _check_mcmr(A, B)
    m, n = B.gshape
    if A.gshape != (m, m):
        raise ValueError(f"A {A.gshape} incompatible with B {B.gshape}")
    r, c = A.grid.height, A.grid.width
    grain = math.lcm(r, c)
    ib = _blocksize(nb, grain, m)
    # bump map (one O(m) host sync): a split at e is legal iff sub[e-1]==0.
    # Splits must stay on the distribution grain (view offsets are
    # stride-multiples), so an illegal split extends by a WHOLE grain.
    sub = np.asarray(get_diagonal(A, offset=-1).local).ravel() if m > 1 \
        else np.zeros(0)
    starts = []
    s = 0
    while s < m:
        e = min(s + ib, m)
        while e < m and sub[e - 1] != 0:
            e = min(e + grain, m)         # never cut a 2x2 block
        starts.append((s, e))
        s = e
    X = B.with_local(alpha * B.local if _nonzero(alpha - 1) else B.local)
    forward = trans                       # effective-upper sweep direction
    if not forward:
        starts = starts[::-1]
    for s, e in starts:
        A11 = redistribute(view(A, rows=(s, e), cols=(s, e)), STAR, STAR)
        a11 = jnp.triu(A11.local, -1)     # upper triangle + the bumps
        B1 = redistribute(view(X, rows=(s, e)), STAR, VR)
        op = a11.T if trans else a11
        if conj:
            op = jnp.conj(op)
        x1 = jnp.linalg.solve(op, B1.local)
        X1 = DistMatrix(x1.astype(X.dtype), B1.gshape, STAR, VR, 0, 0, A.grid)
        X1_mr = redistribute(X1, STAR, MR)
        X = update_view(X, redistribute(X1_mr, MC, MR), rows=(s, e))
        lo, hi = (e, m) if forward else (0, s)
        if lo >= hi:
            continue
        if trans:
            A1p = redistribute(view(A, rows=(s, e), cols=(lo, hi)), STAR, MC)
            a_loc = A1p.local.T
        else:
            A1p = redistribute(view(A, rows=(lo, hi), cols=(s, e)), MC, STAR)
            a_loc = A1p.local
        if conj:
            a_loc = jnp.conj(a_loc)
        X = local_rank_update(X, a_loc, X1_mr.local, rows=(lo, hi),
                              precision=precision)
    return X


# ---------------------------------------------------------------------
# Trr2k / Her2k / Syr2k
# ---------------------------------------------------------------------

def trr2k(uplo: str, alpha, A_mc: DistMatrix, B_mr: DistMatrix,
          beta, C_mc: DistMatrix, D_mr: DistMatrix, gamma, E: DistMatrix,
          precision=None) -> DistMatrix:
    """Triangular rank-2k: E(tri) := alpha A B + beta C D + gamma E(tri),
    other triangle untouched (``El::Trr2k`` with [MC,STAR] x [STAR,MR]
    operand pairs -- the reference's ``LocalTrr2k``)."""
    for X, d in ((A_mc, (MC, STAR)), (C_mc, (MC, STAR)),
                 (B_mr, (STAR, MR)), (D_mr, (STAR, MR))):
        if X.dist != d:
            raise ValueError(f"trr2k operand expected {d}, got {X.dist}")
    _check_mcmr(E)
    mask = _mask_triangle(E, uplo)
    full = alpha * jnp.matmul(A_mc.local, B_mr.local, precision=precision) \
        + beta * jnp.matmul(C_mc.local, D_mr.local, precision=precision)
    return E.with_local(jnp.where(mask, _safe_astype(full + gamma * E.local, E.dtype),
                                  E.local))


def her2k(uplo: str, A: DistMatrix, B: DistMatrix, alpha=1.0, beta=0.0,
          C: DistMatrix | None = None, orient: str = "N", conj: bool = True,
          nb: int | None = None, precision=None) -> DistMatrix:
    """C(tri) := alpha op(A) op(B)^H + conj(alpha) op(B) op(A)^H + beta C(tri)
    (``El::Her2k``; ``conj=False`` gives ``Syr2k`` with ^T and coefficient
    alpha on both products).

    Same panel schedule as :func:`herk` (the ``cholesky::LVar3`` chain via
    the fused ``panel_spread``), two masked storage products per k-panel."""
    if orient != "N":
        A = _orient(A, "C" if conj else "T")
        B = _orient(B, "C" if conj else "T")
    _check_mcmr(A, B)
    m, k = A.gshape
    if B.gshape != (m, k):
        raise ValueError(f"her2k needs conformal A,B; got {A.gshape} vs {B.gshape}")
    r, c = A.grid.height, A.grid.width
    if C is None:
        dts = [A.dtype, B.dtype]
        if isinstance(alpha, complex):
            dts.append(jnp.complex64)
        C = dm_zeros(m, m, MC, MR, A.grid, dtype=jnp.result_type(*dts))
        beta = 0.0
    else:
        _check_mcmr(C)
        if C.gshape != (m, m):
            raise ValueError(f"C shape {C.gshape} != ({m},{m})")
    kb = _blocksize(nb, c, k)
    mask = _mask_triangle(C, uplo)
    alpha2 = jnp.conj(alpha) if conj else alpha
    acc = beta * C.local if _nonzero(beta) else jnp.zeros_like(C.local)
    for s in range(0, k, kb):
        e = min(s + kb, k)
        A1_vc = redistribute(view(A, cols=(s, e)), VC, STAR)
        B1_vc = redistribute(view(B, cols=(s, e)), VC, STAR)
        A1_mc, A1H_mr = panel_spread(A1_vc, conj=conj)
        B1_mc, B1H_mr = panel_spread(B1_vc, conj=conj)
        acc = acc + alpha * jnp.matmul(A1_mc.local, B1H_mr.local, precision=precision) \
            + alpha2 * jnp.matmul(B1_mc.local, A1H_mr.local, precision=precision)
    return C.with_local(jnp.where(mask, _safe_astype(acc, C.dtype), C.local))


def syr2k(uplo: str, A: DistMatrix, B: DistMatrix, alpha=1.0, beta=0.0,
          C: DistMatrix | None = None, orient: str = "N",
          nb: int | None = None, precision=None) -> DistMatrix:
    return her2k(uplo, A, B, alpha, beta, C, orient=orient, conj=False,
                 nb=nb, precision=precision)


# ---------------------------------------------------------------------
# Symm / Hemm / Trmm
# ---------------------------------------------------------------------

def hemm(side: str, uplo: str, A: DistMatrix, B: DistMatrix, alpha=1.0,
         beta=0.0, C: DistMatrix | None = None, conj: bool = True,
         nb: int | None = None, precision=None) -> DistMatrix:
    """C := alpha A B + beta C (side 'L') or alpha B A + beta C ('R') with
    Hermitian A stored in the ``uplo`` triangle (``El::Hemm``;
    ``conj=False`` = ``Symm``).

    TPU-first: materialize the full Hermitian operand once (one
    transpose-exchange redistribution, ``MakeSymmetric``) and run plain
    SUMMA -- the MXU prefers one large dense product over the reference's
    two half-panel accumulations; the one-triangle ACCESS guarantee is kept
    (make_symmetric reads only the stored triangle)."""
    from .level1 import make_symmetric
    _check_mcmr(A, B)
    full = make_symmetric(A, uplo, conj=conj)
    if side.upper().startswith("L"):
        return gemm(full, B, alpha=alpha, beta=beta, C=C, nb=nb, precision=precision)
    return gemm(B, full, alpha=alpha, beta=beta, C=C, nb=nb, precision=precision)


def symm(side: str, uplo: str, A: DistMatrix, B: DistMatrix, alpha=1.0,
         beta=0.0, C: DistMatrix | None = None, nb: int | None = None,
         precision=None) -> DistMatrix:
    return hemm(side, uplo, A, B, alpha, beta, C, conj=False, nb=nb,
                precision=precision)


def trmm(side: str, uplo: str, orient: str, A: DistMatrix, B: DistMatrix,
         alpha=1.0, unit: bool = False, nb: int | None = None,
         precision=None) -> DistMatrix:
    """B := alpha op(tri(A)) B ('L') or alpha B op(tri(A)) ('R')
    (``El::Trmm``).  The triangle (with optional implicit unit diagonal) is
    masked on storage; the product is plain SUMMA."""
    from .level1 import _global_indices
    _check_mcmr(A, B)
    T = jnp.where(_mask_triangle(A, uplo, strict=unit), A.local, 0)
    if unit:
        I, J = _global_indices(A)
        on = (J[None, :] == I[:, None]) & (I[:, None] < A.gshape[0])
        T = jnp.where(on, jnp.asarray(1, A.dtype), T)
    Tm = A.with_local(T)
    if side.upper().startswith("L"):
        return gemm(Tm, B, alpha=alpha, orient_a=orient, nb=nb, precision=precision)
    return gemm(B, Tm, alpha=alpha, orient_b=orient, nb=nb, precision=precision)


# ---------------------------------------------------------------------
# Two-sided transforms (generalized eigenproblem reductions)
# ---------------------------------------------------------------------

def two_sided_trsm(uplo: str, A: DistMatrix, L: DistMatrix,
                   nb: int | None = None, precision=None) -> DistMatrix:
    """Congruence solve: lower -> inv(L) A inv(L)^H, upper -> inv(U)^H A inv(U)
    (``El::TwoSidedTrsm`` -- reduces A x = lambda B x with B = L L^H /
    U^H U to a standard Hermitian problem).  A is read from the ``uplo``
    triangle; the result is returned full (Hermitian)."""
    from .level1 import make_symmetric
    full = make_symmetric(A, uplo, conj=True)
    if uplo.upper().startswith("L"):
        Y = trsm("L", "L", "N", L, full, nb=nb, precision=precision)
        return trsm("R", "L", "C", L, Y, nb=nb, precision=precision)
    Y = trsm("L", "U", "C", L, full, nb=nb, precision=precision)
    return trsm("R", "U", "N", L, Y, nb=nb, precision=precision)


def two_sided_trmm(uplo: str, A: DistMatrix, L: DistMatrix,
                   nb: int | None = None, precision=None) -> DistMatrix:
    """Congruence product: lower -> L^H A L, upper -> U A U^H
    (``El::TwoSidedTrmm`` -- the inverse transform of two_sided_trsm)."""
    from .level1 import make_symmetric
    full = make_symmetric(A, uplo, conj=True)
    if uplo.upper().startswith("L"):
        Y = trmm("L", "L", "C", L, full, nb=nb, precision=precision)
        return trmm("R", "L", "N", L, Y, nb=nb, precision=precision)
    Y = trmm("L", "U", "N", L, full, nb=nb, precision=precision)
    return trmm("R", "U", "C", L, Y, nb=nb, precision=precision)


# ---------------------------------------------------------------------
# MultiShiftTrsm (the Pseudospectra / TriangEig engine)
# ---------------------------------------------------------------------

def _star_vr_colmap(n: int, p: int):
    """Static [STAR,VR] storage-column -> global-column map (zero align):
    (clipped global index per storage column, in-range mask)."""
    lc = -(-n // p)
    q = np.arange(p)[:, None]
    jl = np.arange(lc)[None, :]
    perm = (jl * p + q).reshape(-1)
    return jnp.asarray(np.clip(perm, 0, n - 1)), jnp.asarray(perm < n)


def multishift_trsm(uplo: str, orient: str, A: DistMatrix, shifts,
                    B: DistMatrix, alpha=1.0, nb: int | None = None,
                    precision=None, diag_hook=None) -> DistMatrix:
    """Solve (op(tri(A)) - shifts[j] I) X[:, j] = alpha B[:, j] for all j at
    once (``El::MultiShiftTrsm``, ``src/blas_like/level3/MultiShiftTrsm/``).

    Same blocked sweep as :func:`trsm`; the diagonal-block solve becomes a
    column-batched shifted triangular solve on the [STAR,VR] panel (each
    storage column's shift selected by the static cyclic column permutation
    -- pure local, zero extra communication), and the trailing update is
    shift-free (shifts only touch diagonal blocks).

    ``diag_hook(M, sigma, global_col, global_rows)``, if given, may rewrite
    the shifted diagonal block per column before the solve (TriangEig's
    identity-row replacement rides this)."""
    trans = orient in ("T", "C")
    conj = orient == "C"
    _check_mcmr(A, B)
    m, n = B.gshape
    if A.gshape != (m, m):
        raise ValueError(f"A {A.gshape} incompatible with B {B.gshape}")
    shifts = jnp.asarray(shifts)
    if shifts.shape != (n,):
        raise ValueError(f"shifts must be ({n},), got {shifts.shape}")
    lower = uplo.upper().startswith("L")
    g = A.grid
    r, c = g.height, g.width
    p = r * c
    ib = _blocksize(nb, math.lcm(r, c), m)
    gcol, in_range = _star_vr_colmap(n, p)
    sig_stor = jnp.where(in_range, jnp.take(shifts, gcol), 0)
    # (op(M) - sigma I) = op(M - sigma' I): diagonal untouched by T, conj by C
    sig_eff = jnp.conj(sig_stor) if conj else sig_stor

    X = B.with_local(alpha * B.local if _nonzero(alpha - 1) else B.local)
    starts = list(range(0, m, ib))
    forward = lower != trans
    if not forward:
        starts = starts[::-1]
    for s in starts:
        e = min(s + ib, m)
        A11 = redistribute(view(A, rows=(s, e), cols=(s, e)), STAR, STAR)
        a11 = jnp.tril(A11.local) if lower else jnp.triu(A11.local)
        B1 = redistribute(view(X, rows=(s, e)), STAR, VR)
        d = a11.shape[0]
        eye = jnp.eye(d, dtype=a11.dtype)
        rowg = s + jnp.arange(d)

        def _one(sg, jg, b):
            M = a11 - sg * eye
            if diag_hook is not None:
                M = diag_hook(M, sg, jg, rowg)
            return lax.linalg.triangular_solve(
                M, b[:, None], left_side=True, lower=lower,
                transpose_a=trans, conjugate_a=conj)[:, 0]

        x1 = jax.vmap(_one, in_axes=(0, 0, 1), out_axes=1)(
            sig_eff.astype(a11.dtype), gcol, B1.local)
        X1 = DistMatrix(x1, B1.gshape, STAR, VR, 0, 0, g)
        X1_mr = redistribute(X1, STAR, MR)
        X = update_view(X, redistribute(X1_mr, MC, MR), rows=(s, e))
        lo, hi = (e, m) if forward else (0, s)
        if lo >= hi:
            continue
        if trans:
            A1p = redistribute(view(A, rows=(s, e), cols=(lo, hi)), STAR, MC)
            a_loc = A1p.local.T
        else:
            A1p = redistribute(view(A, rows=(lo, hi), cols=(s, e)), MC, STAR)
            a_loc = A1p.local
        if conj:
            a_loc = jnp.conj(a_loc)
        upd = jnp.matmul(a_loc, X1_mr.local, precision=precision)
        rest = view(X, rows=(lo, hi))
        X = update_view(X, rest.with_local(rest.local - upd.astype(X.dtype)),
                        rows=(lo, hi))
    return X
