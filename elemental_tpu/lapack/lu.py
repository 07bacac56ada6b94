"""LU with partial pivoting (HPL-style, look-ahead pipelined) + permutation
utilities.

Reference: Elemental ``src/lapack_like/factor/LU.cpp`` +
``LU/{Panel,SolveAfter}.hpp`` and ``src/lapack_like/perm/`` (DistPermutation,
ApplyRowPivots) -- BASELINE.json's "LU with partial pivoting" config.

TPU-first redesign of the panel (SURVEY.md §4.4 / §8.3 item 2): the
reference's ``lu::Panel`` runs one MAXLOC AllReduce + one SendRecv PER
COLUMN -- a latency wall.  Here the whole current panel is gathered to
[STAR,STAR] (one collective) and factored REDUNDANTLY on every device with
a local ``lax.fori_loop``: identical deterministic results everywhere, so
pivot search costs zero communication.  The panel's composed row
permutation is applied to the trailing rows with one traced gather/scatter
on the storage array (the analog of HPL's row-broadcast swap).

Communication-avoiding panel (``panel='calu'``, ISSUE 6): tournament
pivoting replaces even the replicated per-column pivot chain -- per-grid-
row slab LUs, a log-depth playoff of candidate pivot blocks, ONE batched
storage-level row permutation per panel, an unpivoted MXU-friendly
refactorization, and a one-psum row-block solve.  See :func:`lu` and the
README's "Communication-avoiding LU" section; ``panel='classic'``
(default) is byte-for-byte the schedule described above.

Look-ahead schedule (the HPL pipeline; default on)
--------------------------------------------------
The classic right-looking driver serializes panel -> swap -> solve ->
update every step, so the latency-bound replicated panel factorization
sits on the critical path ``n/nb`` times.  The pipelined driver instead
splits step k's trailing update by columns into (a) the NEXT panel's
strip and (b) the wide remainder:

    swap + write back panel k                    (from the carried factor)
    U_k  := L11^{-1} A(k, k+1:)                  (one row-block solve)
    strip := A22[:, :nb] - L21 U_k[:, :nb]       (a: narrow update)
    factor panel k+1 from ``strip``              (off the critical path)
    rest := A22[:, nb:] - L21 U_k[:, nb:]        (b: wide MXU update)

The strip/rest operands are captured BEFORE any writeback, so the panel
k+1 factorization and the wide remainder matmul share no data dependence
and XLA is free to overlap them (async collectives on a grid, scheduler
freedom on one chip).  Everything stays one traced program per
(shape, grid): no host sync between phases.

Precision split (``update_precision``)
--------------------------------------
``precision`` governs the panel factorization and the triangular/row-block
solves (default f32 accumulation via :func:`_hi`).  ``update_precision``,
when given, applies ONLY to the trailing ``L21 @ U12`` updates -- passing
``lax.Precision.DEFAULT`` runs them on the bf16 MXU path (~6x the f32-class
matmul rate on TPU).  This is opt-in: bf16 trailing updates raise the
``||P A - L U|| / ||A||`` residual from ~1e-6 to the ~1e-3 level at
n=16384 (each entry of the Schur complement accumulates bf16 rounding
``n/nb`` times), which is still small relative to partial pivoting's
growth bound but well above the f32 default.  Leave it ``None`` for
bit-equivalent-to-classic factors.

Phases (``timer``)
------------------
The driver marks panel / swap / solve / update (/tail) with the scoped
form of its hook (``with tm.phase(phase, k)``, :mod:`elemental_tpu.obs`).
With ``timer=None`` (default) the driver jits as one fused program whose
ops carry ``el.lu/k<step>/<phase>`` in their names: a device trace is
split by them.  Pass an ``elemental_tpu.obs.PhaseTimer`` and call ``lu``
EAGERLY (outside jit) and the same blocks also synchronize at every
boundary and charge per-step wall-clock (``timer.report()``).

Data-dependent pivots are traced values, so the whole factorization jits;
the packed L\\U layout and the permutation-vector convention follow LAPACK
getrf (perm[i] = original index of the row now at position i).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ..core.compat import shard_map
from ..core.dist import MC, MR, STAR, VC, VR
from ..core.distmatrix import DistMatrix
from ..core.view import view, update_view
from ..redist.engine import (apply_fault, move_rows, permute_rows_storage,
                             redistribute)
from ..redist.quantize import check_comm_precision, quantizable
from ..blas.level3 import _blocksize, _check_mcmr, local_rank_update, trsm

from ..kernels import default_inners as _default_inners
from ..kernels import resolve_panel as _resolve_panel


def _hi(precision):
    """Precision policy of the lapack layer: with ``precision=None`` every
    matmul in a factorization/reduction driver runs at full f32
    accumulation (``Precision.HIGHEST``), matching the reference's f32
    BLAS semantics -- the default (bf16-input) matmul precision costs
    ~1e-2-level factor error on TPU, a silent accuracy downgrade.  An
    explicitly passed precision (including ``lax.Precision.DEFAULT`` for
    bf16-MXU throughput on the trailing updates) is honored unchanged.

    Every public factor/solve driver of this layer resolves its
    ``precision`` argument through here ON ENTRY, so the trailing updates
    and the triangular sweeps -- most of the flops -- run under the same
    policy as the panel pieces, not at whatever ``None`` means to
    ``jnp.matmul`` on the backend (full float32 on the CPU, one bf16 pass
    on a TPU)."""
    return precision if precision is not None else lax.Precision.HIGHEST


# The null hook, the driver-entry hook resolver and the driver-scope
# decorator live in the observability subsystem; cholesky, qr and abft
# import ``_phase_hook`` from here.
from ..obs.tracer import (NULL_HOOK, phase_hook as _phase_hook,
                          scoped as _scoped)
from ..obs import metrics as _metrics


# ---------------------------------------------------------------------
# permutation utilities (the DistPermutation analog)
# ---------------------------------------------------------------------

def permute_rows(B: DistMatrix, perm, inverse: bool = False) -> DistMatrix:
    """B[perm, :] as a DistMatrix (``DistPermutation::PermuteRows``).

    Zero-aligned [MC,MR] rides the engine's one-shot storage gather
    (``permute_rows_storage``, the batched-permutation fast path -- no
    explicit collective rounds); misaligned inputs keep the historical
    [STAR,VR] route: rows replicated there, so the traced-index gather is
    pure-local, and two engine hops re-land [MC,MR]."""
    _check_mcmr(B)
    if (B.calign, B.ralign) == (0, 0):
        return permute_rows_storage(B, perm, inverse=inverse)
    Bvr = redistribute(B, STAR, VR)
    p = jnp.argsort(perm) if inverse else perm
    out = Bvr.with_local(Bvr.local[p, :])
    return redistribute(out, MC, MR)


def permute_cols(B: DistMatrix, perm, inverse: bool = False) -> DistMatrix:
    """B[:, perm] as a DistMatrix (``DistPermutation::PermuteCols``).

    Rides [VC,STAR]: columns replicated there, so the traced-index gather is
    pure-local; two engine hops re-land [MC,MR]."""
    _check_mcmr(B)
    Bvc = redistribute(B, VC, STAR)
    p = jnp.argsort(perm) if inverse else perm
    out = Bvc.with_local(Bvc.local[:, p])
    return redistribute(out, MC, MR)


def _apply_swaps_moved(A: DistMatrix, T, S, valid) -> DistMatrix:
    """Move global rows ``S`` to positions ``T`` in one batched pass,
    dropping entries where ``valid`` is False (sentinel padding from
    :func:`_moved_rows`).  Thin wrapper over the engine's storage-level
    batched-permutation fast path (``redist.engine.move_rows``), kept
    under its historical name for this module's importers."""
    return move_rows(A, T, S, valid)


# ---------------------------------------------------------------------
# replicated panel factorization
# ---------------------------------------------------------------------

def _panel_lu_unb(P, nbw: int):
    """Unblocked partial-pivot LU of a replicated (M, nbw) panel.

    Runs identically on every device (replicated input, deterministic) --
    the TPU answer to ``lu::Panel``'s per-column MAXLOC+SendRecv.
    Returns (packed L\\U panel, composed row permutation of the panel:
    output row i came from input row perm[i])."""
    M = P.shape[0]
    ridx = jnp.arange(M)
    cidx = jnp.arange(nbw)

    def body(j, state):
        P, perm = state
        col = P[:, j]
        cand = jnp.where(ridx >= j, jnp.abs(col), -jnp.inf)
        p = jnp.argmax(cand)
        rowj, rowp = P[j], P[p]
        P = P.at[j].set(rowp).at[p].set(rowj)
        pj, pp = perm[j], perm[p]
        perm = perm.at[j].set(pp).at[p].set(pj)
        pivval = P[j, j]
        l = jnp.where(ridx > j, P[:, j] / pivval, jnp.zeros_like(col))
        P = P.at[:, j].set(jnp.where(ridx > j, l, P[:, j]))
        urow = jnp.where(cidx > j, P[j], jnp.zeros_like(P[j]))
        P = P - jnp.outer(l, urow)
        return P, perm

    return lax.fori_loop(0, nbw, body, (P, jnp.arange(M)))


def _panel_lu(P, nbw: int, precision=None, inners=None):
    """Multi-level blocked panel: ``inners``-wide chunk recursion + matmul
    sub-updates.  The unblocked loop's per-column rank-1 update streams the
    whole chunk each iteration (bandwidth-bound at nbw sequential passes);
    narrowing the innermost chunk to 32 columns cuts that traffic ~nbw/32
    times while every chunk-to-chunk update is an MXU matmul.

    Returns (packed panel, composed row permutation of the panel)."""
    if inners is None:
        inners = _default_inners()
    if not inners or nbw <= inners[-1]:
        return _panel_lu_unb(P, nbw)
    step, rest = inners[0], inners[1:]
    if nbw <= step:
        return _panel_lu(P, nbw, precision, rest)
    M = P.shape[0]
    perm = jnp.arange(M)
    for s in range(0, nbw, step):
        e = min(s + step, nbw)
        w = e - s
        sub, sperm = _panel_lu(P[s:, s:e], w, precision, rest)
        rows = jnp.take(P[s:], sperm, axis=0)          # apply swaps to block-row
        rows = rows.at[:, s:e].set(sub)
        if e < nbw:
            L11 = jnp.tril(sub[:w], -1) + jnp.eye(w, dtype=P.dtype)
            U12 = lax.linalg.triangular_solve(
                L11, rows[:w, e:], left_side=True, lower=True,
                unit_diagonal=True)
            rows = rows.at[:w, e:].set(U12)
            upd = jnp.matmul(sub[w:, :w], U12, precision=precision)
            rows = rows.at[w:, e:].set(rows[w:, e:] - upd.astype(P.dtype))
        P = P.at[s:].set(rows)
        perm = perm.at[s:].set(jnp.take(perm[s:], sperm, axis=0))
    return P, perm


def _panel_dispatch(P, nbw: int, precision=None, plan=None):
    """Route one replicated panel through the resolved ``panel_impl``
    plan (``kernels.PanelPlan``): the fused Pallas kernel when the plan
    says so AND the panel passes the static VMEM/dtype gate, else the
    XLA chunk ladder with the plan's ``inners``.  ``plan=None`` is the
    status-quo ladder -- every historical caller is unchanged."""
    if plan is not None and plan.use_pallas(P.shape, P.dtype):
        from ..kernels import lu_panel
        return lu_panel(P, nbw, precision, inner=plan.pallas_inner)
    inners = plan.inners if plan is not None else None
    return _panel_lu(P, nbw, precision, inners)


# ---------------------------------------------------------------------
# CALU tournament-pivoted panel (communication-avoiding LU, cf.
# Grigori/Demmel/Xiang and the TPU distributed-linear-algebra paper
# arXiv 2112.09017): each grid row factors its cyclic slab of the panel
# with ordinary partial pivoting, the per-slab candidate pivot blocks
# reduce in a log-depth pairwise-LU playoff tree, and the winning rows
# are applied as ONE composed row permutation per panel.  The permuted
# panel then factors WITHOUT pivoting: an nb x nb unpivoted diagonal
# factorization plus a single MXU matmul for the whole L21 block --
# no per-column argmax or data-dependent row swap over the panel height,
# which is exactly the latency wall of the classic panel.
# ---------------------------------------------------------------------

def _playoff_perm(V, ncol: int):
    """Pivot ORDER of a masked partial-pivot LU sweep over a (possibly
    zero-padded) block: returns the composed permutation only (the factor
    values are discarded -- playoffs select rows, the real factorization
    happens once on the winners).  Divisions are guarded so all-zero
    padding rows flow through as zeros instead of NaNs."""
    Mp, w = V.shape
    ridx = jnp.arange(Mp)
    cidx = jnp.arange(w)

    def body(j, state):
        V, perm = state
        cand = jnp.where(ridx >= j, jnp.abs(V[:, j]), -jnp.inf)
        p = jnp.argmax(cand)
        rowj, rowp = V[j], V[p]
        V = V.at[j].set(rowp).at[p].set(rowj)
        pj, pp = perm[j], perm[p]
        perm = perm.at[j].set(pp).at[p].set(pj)
        piv = V[j, j]
        safe = jnp.where(piv == 0, jnp.ones_like(piv), piv)
        l = jnp.where(ridx > j, V[:, j] / safe, jnp.zeros_like(V[:, j]))
        V = V.at[:, j].set(jnp.where(ridx > j, l, V[:, j]))
        urow = jnp.where(cidx > j, V[j], jnp.zeros_like(V[j]))
        return V - jnp.outer(l, urow), perm

    _, perm = lax.fori_loop(0, min(ncol, Mp), body, (V, jnp.arange(Mp)))
    return perm


def _tournament_pivots(P, nbw: int, r: int):
    """The CALU tournament: composed panel permutation (perm[i] = original
    row now at position i) whose first ``nbw`` entries are the playoff
    winners.  Runs replicated and deterministic on every device (same
    zero-communication pattern as the classic replicated panel): slab
    membership mirrors the [MC,*] ownership map (global row i lives in
    grid row i % r), so the simulated tournament selects exactly the
    pivots a message-passing CALU over the grid rows would."""
    M = P.shape[0]
    lslab = max(-(-M // r), nbw)
    sidx = jnp.arange(lslab)[None, :] * r + jnp.arange(r)[:, None]
    ok = sidx < M                                       # (r, lslab)
    vals = jnp.where(ok[:, :, None], P[jnp.clip(sidx, 0, M - 1)], 0)
    gidx = jnp.where(ok, sidx, M)                       # sentinel M = padding
    # round 0: every slab's local partial-pivot sweep (vmapped -- the
    # replicated image of r independent, communication-free local LUs)
    sperm = jax.vmap(lambda v: _playoff_perm(v, nbw))(vals)
    top = sperm[:, :nbw]
    cvals = jnp.take_along_axis(vals, top[:, :, None], axis=1)
    cidx = jnp.take_along_axis(gidx, top, axis=1)       # (r, nbw)
    # log-depth pairwise playoffs (odd participant gets a bye)
    nblk = r
    while nblk > 1:
        half, odd = nblk // 2, nblk % 2
        lo_v, hi_v = cvals[:half], cvals[half:2 * half]
        lo_i, hi_i = cidx[:half], cidx[half:2 * half]
        st_v = jnp.concatenate([lo_v, hi_v], axis=1)    # (half, 2*nbw, nbw)
        st_i = jnp.concatenate([lo_i, hi_i], axis=1)
        pperm = jax.vmap(lambda v: _playoff_perm(v, nbw))(st_v)
        wtop = pperm[:, :nbw]
        wv = jnp.take_along_axis(st_v, wtop[:, :, None], axis=1)
        wi = jnp.take_along_axis(st_i, wtop, axis=1)
        if odd:
            wv = jnp.concatenate([wv, cvals[2 * half:]], axis=0)
            wi = jnp.concatenate([wi, cidx[2 * half:]], axis=0)
        cvals, cidx = wv, wi
        nblk = half + odd
    win = cidx[0]                                       # (nbw,) global rows
    # compose the one-shot permutation: winner j swaps into position j
    # (a padding sentinel degenerates to a no-op swap; only reachable on
    # exactly-singular panels, where classic pivoting is arbitrary too)
    def body(j, state):
        perm, invp = state
        w = jnp.where(win[j] < M, win[j], perm[j])
        tp = invp[w]
        pj = perm[j]
        perm = perm.at[j].set(w).at[tp].set(pj)
        invp = invp.at[w].set(j).at[pj].set(tp)
        return perm, invp

    perm, _ = lax.fori_loop(0, nbw, body, (jnp.arange(M), jnp.arange(M)))
    return perm


def _lu_nopiv(W, precision=None, bs: int = 256, *, block_kernel=None):
    """Unpivoted blocked LU of a square block (packed L\\U, unit-lower L).
    The CALU diagonal factorization: the tournament already fixed the
    pivot order, so no argmax / row motion remains -- diagonal blocks run
    the plain recurrence, off-diagonal blocks are triangular solves and
    one MXU matmul per step.

    The recurrence of one ``bs x bs`` sub-block has two lowerings of ONE
    algorithm.  ``unb`` is a ``lax.fori_loop`` of XLA ops: a divide, a
    column store and an outer-product subtract a column, whose launches
    and re-laid carry cost 4.2 us a column on a v5e.  With
    ``block_kernel`` (the caller's static choice, from what ITS input
    shows: ``lapack/mixed.py:_diag_blocks_in_vmem``; real blocks only) it
    is that callable, ``kernels.lu_nopiv_block`` as the caller's chips run
    it: the Pallas kernel ``el_lu_nopiv_block``, the sub-block resident in
    VMEM for all its columns.  The blocked outer loop and the sub-block
    order ``bs`` are the same in both: a kernel step costs the sub-block's
    area in tile passes, the triangular solves and the matmul between
    sub-blocks fall as 1 / bs, and the whole HPL-MxP solve on the chip
    reads 0.36746 s at 128, 0.36744 at 256 and 0.36972 at 512 (PERF.md 6,
    PR 46)."""
    b = W.shape[0]

    def unb(B):
        n = B.shape[0]
        idx = jnp.arange(n)

        def body(j, B):
            l = jnp.where(idx > j, B[:, j] / B[j, j], jnp.zeros_like(B[:, j]))
            B = B.at[:, j].set(jnp.where(idx > j, l, B[:, j]))
            urow = jnp.where(idx > j, B[j], jnp.zeros_like(B[j]))
            return B - jnp.outer(l, urow)

        return lax.fori_loop(0, n, body, B)

    if block_kernel is not None:
        unb = block_kernel
    if b <= bs:
        return unb(W)
    for s in range(0, b, bs):
        e = min(s + bs, b)
        blk = unb(W[s:e, s:e])
        W = W.at[s:e, s:e].set(blk)
        if e < b:
            L11 = jnp.tril(blk, -1) + jnp.eye(e - s, dtype=W.dtype)
            U12 = lax.linalg.triangular_solve(
                L11, W[s:e, e:], left_side=True, lower=True,
                unit_diagonal=True)
            L21 = lax.linalg.triangular_solve(
                jnp.triu(blk), W[e:, s:e], left_side=False, lower=False)
            W = W.at[s:e, e:].set(U12).at[e:, s:e].set(L21)
            upd = jnp.matmul(L21, U12, precision=_hi(precision))
            W = W.at[e:, e:].set(W[e:, e:] - upd.astype(W.dtype))
    return W


def _upper_inv(U, nbw: int, precision=None, bs: int = 256):
    """Inverse of a non-unit upper-triangular block with matmul assembly
    (the upper sibling of :func:`_unit_lower_inv`) -- turns the CALU
    ``L21 := A21 U11^{-1}`` panel solve into MXU matmuls
    (:func:`_tri_matmul`: one a block column of the inverse, which is
    built into zeros, so what lies under a ``bs`` block's diagonal is an
    exact zero)."""
    dt = U.dtype
    if nbw <= bs:
        return lax.linalg.triangular_solve(
            U, jnp.eye(nbw, dtype=dt), left_side=True, lower=False)
    Ui = jnp.zeros((nbw, nbw), dt)
    for s in range(0, nbw, bs):
        e = min(s + bs, nbw)
        Uikk = lax.linalg.triangular_solve(
            U[s:e, s:e], jnp.eye(e - s, dtype=dt), left_side=True,
            lower=False)
        if s > 0:
            corr = jnp.matmul(
                jnp.matmul(Ui[:s, :s], U[:s, s:e], precision=_hi(precision)),
                Uikk, precision=_hi(precision))
            Ui = Ui.at[:s, s:e].set(-corr.astype(dt))
        Ui = Ui.at[s:e, s:e].set(Uikk)
    return Ui


#: Block width of a panel's product against a block-triangular inverse
#: (:func:`_tri_matmul`): a multiple of every builder's own block
#: (``_upper_inv`` / ``_unit_lower_inv`` 256, ``cholesky._potrf_inv_impl``
#: 512), so what lies past a block's diagonal is zero by construction.
TRI_BLOCK = 512


def _tri_matmul(A, B, side: str, precision):
    """``A @ B`` where one operand is the (w, w) inverse of a triangular
    block as :func:`_upper_inv`, :func:`_unit_lower_inv` and
    ``cholesky._potrf_inv_impl`` build it: assembled block by block into
    zeros, so everything on the other side of the block diagonal is an
    EXACT zero, which the compiler cannot know.  The dense product
    multiplies those zeros (``2 m w^2`` flops for a triangle's
    ``m w^2``); here the contraction STOPS at each block's diagonal:

    * ``side='right'``: B upper-triangular (``X @ Ui``, ``X @ Li^H``);
      column block j is ``A[:, :e_j] @ B[:e_j, s_j:e_j]``;
    * ``side='left'``: A lower-triangular (``Li @ Y``); row block i is
      ``A[s_i:e_i, :e_i] @ B[:e_i, :]``.

    With ``w = t TRI_BLOCK`` that is ``(1 + 1/t) / 2`` of the dense
    product's flops (62.5 % at the cells' w = 2048).  Only terms that are
    exactly zero leave the sums: the result differs from the dense one by
    a float32 dot's summation order.  The rule is in the shape:
    ``w < 2 TRI_BLOCK`` is the one dense matmul.  Ticks the trace-time
    counter ``panel_tri_product{kind}``, ``blocked`` | ``dense``."""
    w = B.shape[0]
    c = TRI_BLOCK
    if w < 2 * c:
        _metrics.inc("panel_tri_product", kind="dense")
        return jnp.matmul(A, B, precision=precision)
    _metrics.inc("panel_tri_product", kind="blocked")
    edges = [(s, min(s + c, w)) for s in range(0, w, c)]
    if side == "right":
        return jnp.concatenate(
            [jnp.matmul(A[:, :e], B[:e, s:e], precision=precision)
             for s, e in edges], axis=1)
    return jnp.concatenate(
        [jnp.matmul(A[s:e, :e], B[:e, :], precision=precision)
         for s, e in edges], axis=0)


def _nopiv_panel(Pp, nbw: int, precision=None):
    """Unpivoted factorization of an already-permuted (M, nbw) panel:
    packed ``[L11\\U11; L21]`` with ``L21 = A21 U11^{-1}`` as a product
    with the block's inverse over its non-zero blocks (:func:`_tri_matmul`;
    one dense matmul at a TSQR width).
    Shared by the CALU panel (winners on top) and the TSQR Householder
    reconstruction in ``qr.py`` (LU of ``Q1 - S``)."""
    Wf = _lu_nopiv(Pp[:nbw], precision)
    if Pp.shape[0] == nbw:          # the last panel: nothing under its block
        return Wf
    Ui = _upper_inv(jnp.triu(Wf), nbw, precision)
    L21 = _tri_matmul(Pp[nbw:], Ui, "right", _hi(precision)
                      ).astype(Pp.dtype)
    return jnp.concatenate([Wf, L21], axis=0)


def _calu_panel(P, nbw: int, r: int, precision=None):
    """CALU panel factorization of a replicated (M, nbw) panel: tournament
    pivot selection over ``r`` grid-row slabs + unpivoted refactorization
    of the permuted panel.  Same ``(packed, perm)`` contract as
    :func:`_panel_lu`, so the look-ahead / crossover machinery consumes it
    unchanged.  With ``r == 1`` the tournament IS partial pivoting (one
    slab, winners = the PP pivots), so the classic panel is called
    directly -- bit-identical pivots on single-row grids."""
    M = P.shape[0]
    if r <= 1 or M <= nbw:
        return _panel_lu(P, nbw, precision)
    perm = _tournament_pivots(P, nbw, r)
    Pp = jnp.take(P, perm, axis=0)
    return _nopiv_panel(Pp, nbw, precision), perm


def _unit_lower_inv(L11, nbw: int, precision=None, bs: int = 256):
    """Inverse of a unit-lower (nbw, nbw) panel block with matmul assembly
    (small triangular_solve only at ``bs`` diagonal blocks) -- turns the
    U12 := L11^{-1} A12 panel solve into MXU matmuls (:func:`_tri_matmul`:
    one a block row of the inverse, which is built into zeros, so what
    lies right of a ``bs`` block's diagonal is an exact zero)."""
    dt = L11.dtype
    if nbw <= bs:
        return lax.linalg.triangular_solve(
            L11, jnp.eye(nbw, dtype=dt), left_side=True, lower=True,
            unit_diagonal=True)
    Li = jnp.zeros((nbw, nbw), dt)
    for s in range(0, nbw, bs):
        e = min(s + bs, nbw)
        Likk = lax.linalg.triangular_solve(
            L11[s:e, s:e], jnp.eye(e - s, dtype=dt), left_side=True,
            lower=True, unit_diagonal=True)
        if s > 0:
            corr = jnp.matmul(
                Likk, jnp.matmul(L11[s:e, :s], Li[:s, :s],
                                 precision=_hi(precision)),
                precision=_hi(precision))
            Li = Li.at[s:e, :s].set(-corr.astype(dt))
        Li = Li.at[s:e, s:e].set(Likk)
    return Li


def _moved_rows(pperm, nbw: int):
    """Indices (into the trailing block) actually displaced by the composed
    panel permutation, padded to the static size 2*nbw with an out-of-range
    sentinel.  A composition of nbw swaps touches at most 2*nbw positions,
    so gather/scatter of just these rows replaces a full trailing-matrix
    row permutation (the dominant swap cost at large n)."""
    M = pperm.shape[0]
    k = min(2 * nbw, M)
    moved = pperm != jnp.arange(M)
    idx = jnp.nonzero(moved, size=k, fill_value=M)[0]
    src = pperm[jnp.clip(idx, 0, M - 1)]
    return idx, src


# ---------------------------------------------------------------------
# one-collective row-block solve (the CALU schedule's U12 path)
# ---------------------------------------------------------------------

@partial(jax.jit, static_argnums=(2, 3))
def _rowblock_solve_jit(Ablk: DistMatrix, Li11, precision, wire=None):
    """``U = Li11 @ Ablk`` for an (nbw, w) [MC,MR] row block, landing
    [STAR,MR] in ONE psum round.

    The classic schedule moves the row block to [STAR,VR] (an all_to_all),
    multiplies locally, and promotes VR -> MR (an all_gather): two
    collective rounds per panel.  Here each device contracts the
    replicated ``Li11`` against only the block rows it already stores
    (columns ``mc + r*iLoc`` of ``Li11``) and one ``psum`` over the grid
    column completes the product -- the contraction is genuinely
    distributed over grid rows, r-fold less panel-solve compute per
    device AND one round instead of two.

    ``wire='bf16'`` runs the psum on a bfloat16 payload (the
    ``comm_precision`` path: reductions never ride int8 -- integer
    accumulation would overflow the block scale -- so both quantized
    modes reduce at bf16; local math stays at ``precision``)."""
    g = Ablk.grid
    r = g.height
    nbw = Ablk.gshape[0]
    out_meta = DistMatrix(None, Ablk.gshape, STAR, MR, 0, 0, g)

    def f(ab, L):
        mc = lax.axis_index("mc")
        lr = ab.local.shape[0]
        cols = mc + r * jnp.arange(lr)
        okc = cols < nbw
        Lsub = jnp.take(L, jnp.clip(cols, 0, nbw - 1), axis=1)
        Lsub = jnp.where(okc[None, :], Lsub, 0)
        part = jnp.matmul(Lsub, ab.local, precision=precision)
        if wire == "bf16":
            out = lax.psum(part.astype(jnp.bfloat16), "mc").astype(part.dtype)
        else:
            out = lax.psum(part, "mc")
        return DistMatrix(out, ab.gshape, STAR, MR, 0, ab.ralign, g)

    from jax.sharding import PartitionSpec as P
    return shard_map(
        f, mesh=g.mesh, in_specs=(Ablk.spec, P(None, None)),
        out_specs=out_meta.spec, check_vma=False,
    )(Ablk, Li11)


# ---------------------------------------------------------------------
# blocked right-looking LU with look-ahead
# ---------------------------------------------------------------------

def _local_lu(A: DistMatrix, nb: int | None, precision,
              update_precision=None, lookahead: bool = True, timer=None,
              plan=None):
    """Sequential (p == 1) path: on a 1x1 grid the storage array IS the
    global matrix, so the blocked loop fuses into one XLA program with no
    redistribute sub-computation boundaries (the local ``Matrix<T>``
    dispatch of the reference).  ``lookahead=True`` runs the pipelined
    schedule from the module docstring; ``False`` keeps the classic
    right-looking order (the A/B baseline)."""
    a, perm = _local_lu_array(A.local, A.gshape[0], A.gshape[1],
                              max(nb or 1024, 1), precision,
                              update_precision, lookahead, timer, plan)
    return A.with_local(a), perm


def _local_lu_array(a, m: int, n: int, ib: int, precision,
                    update_precision=None, lookahead: bool = True,
                    timer=None, plan=None):
    """Blocked LU of a plain (replicated) array: the sequential engine
    behind both the 1x1-grid path and the distributed loop's
    crossover-to-local tail.  Returns ``(packed LU array, perm)``."""
    kend = min(m, n)
    perm = jnp.arange(m)
    upd = precision if update_precision is None else update_precision
    tm = timer if timer is not None else NULL_HOOK
    tm.start()
    if lookahead:
        w0 = min(ib, kend)
        with tm.phase("panel", 0) as ph:
            nxt = _panel_dispatch(a[:, :w0], w0, precision, plan)
            ph.done(nxt)
    for k, s in enumerate(range(0, kend, ib)):
        e = min(s + ib, kend)
        nbw = e - s
        if lookahead:
            Pf, pperm = nxt
        else:
            with tm.phase("panel", k) as ph:
                Pf, pperm = _panel_dispatch(a[s:, s:e], nbw, precision, plan)
                ph.done(Pf, pperm)
        with tm.phase("swap", k) as ph:
            perm = perm.at[s:].set(jnp.take(perm[s:], pperm, axis=0))
            # full trailing-block gather + contiguous writeback (TPU
            # scatters of dynamic row sets benchmark SLOWER than this full
            # gather).  Memory: the rows are gathered straight from ``a``
            # (no a[s:] slice copy), and pperm is a permutation, always in
            # bounds, so mode='clip' (the default 'fill' adds a select
            # over a second copy of the block).  Each was a 4 GiB temp at
            # N = 32768, and together they put lu_solve past a 16 GB chip.
            a = a.at[s:].set(jnp.take(a, s + pperm, axis=0, mode="clip"))
            ph.done(a)
        with tm.phase("panel", k):
            a = a.at[s:, s:e].set(Pf)
        if e >= n:
            continue
        with tm.phase("solve", k) as ph:
            Li11 = _unit_lower_inv(jnp.tril(Pf[:nbw], -1)
                                   + jnp.eye(nbw, dtype=a.dtype),
                                   nbw, precision)
            # the joined row block is a value of its own BEFORE the
            # write-back below: fused into the write-back's fusion (a second
            # output of it) it made the strip update, which reads the
            # pre-writeback ``a``, wait for the write, and the TPU compiler
            # copied the whole buffer every step (PERF.md 6, PR 47)
            U1n = lax.optimization_barrier(
                _tri_matmul(Li11, a[s:e, e:], "left", _hi(precision)
                            ).astype(a.dtype))
            ph.done(U1n)
        if not lookahead or e >= kend:
            with tm.phase("solve", k):
                a = a.at[s:e, e:].set(U1n)
            if e < m:
                with tm.phase("update", k) as ph:
                    u = jnp.matmul(Pf[nbw:], U1n, precision=upd)
                    a = a.at[e:, e:].set(a[e:, e:] - u.astype(a.dtype))
                    ph.done(a)
            continue
        # look-ahead: (a) narrow strip update -> factor panel k+1 off the
        # critical path -> (b) wide remainder update.  Both updates read
        # the pre-writeback ``a``, so XLA sees them as independent.
        e2 = min(e + ib, kend)
        w = e2 - e
        with tm.phase("update", k):
            L21 = Pf[nbw:]
            strip = a[e:, e:e2] - jnp.matmul(L21, U1n[:, :w],
                                             precision=upd).astype(a.dtype)
        with tm.phase("panel", k + 1) as ph:
            nxt = _panel_dispatch(strip, w, precision, plan)
            ph.done(nxt)
        with tm.phase("solve", k):
            a = a.at[s:e, e:].set(U1n)
        with tm.phase("update", k) as ph:
            if e2 < n:
                rest = a[e:, e2:] - jnp.matmul(L21, U1n[:, w:],
                                               precision=upd).astype(a.dtype)
                a = a.at[e:, e2:].set(rest)
            # the strip region a[e:, e:e2] is dead from here on: step k+1's
            # swap + panel writeback fully overwrite it, so skipping its
            # writeback saves one (m-e) x nb store per step
            ph.done(a)
    return a, perm


#: default crossover-to-local threshold for the look-ahead schedule (the
#: Cholesky PR-2 trade, same default): once the trailing block is at most
#: this size, ONE [STAR,STAR] gather + a replicated local finish replaces
#: the remaining per-step collective latency.  A trailing t x t block
#: costs ~t/nb more panel gathers + solve rounds distributed, vs one
#: gather of t^2 words here -- latency-bound for small t on real meshes.
_CROSSOVER = 4096


@_scoped("el.lu")
def lu(A: DistMatrix, nb: int | str | None = None, precision=None,
       update_precision=None, lookahead: bool | str = True,
       crossover: int | str | None = None, panel: str = "classic",
       panel_impl: str | None = None, inners=None,
       comm_precision: str | None = None, redist_path: str | None = None,
       timer=None, health=None, abft=None):
    """Blocked right-looking LU with partial pivoting and look-ahead.

    Returns (LU, perm): LU holds unit-lower L below the diagonal and U on
    and above it (LAPACK getrf packing); perm is a traced length-m vector
    with perm[i] = original index of the row now at position i, so
    ``P A = L U`` with ``(P A)[i] = A[perm[i]]``.

    ``crossover`` is the trailing-block size at which the distributed loop
    gathers the remaining (rows x cols <= crossover^2) block once,
    finishes it with the replicated sequential kernel, and applies the
    tail's row permutation in one storage-level pass (``None`` =
    :data:`_CROSSOVER` with look-ahead, disabled classic; 0 never crosses
    over).  ``lookahead`` selects the pipelined schedule (module
    docstring); ``update_precision`` optionally lowers ONLY the trailing
    ``L21 @ U12``
    updates (e.g. ``lax.Precision.DEFAULT`` for bf16-MXU throughput at a
    documented ~1e-3 residual cost, and nothing repairs it afterwards:
    where the operand needs no pivoting, ``mixed_solve`` runs the same
    one-pass updates with EXPLICITLY rounded operands, the same arithmetic
    on every backend, and refines the answer on the device back to the
    float32 level, 6e-9 where the unrefined one reads 4e-6 at n = 384;
    ``lapack/mixed.py``); ``timer`` enables eager per-phase
    wall-clock attribution (``elemental_tpu.obs.PhaseTimer``).

    ``panel`` selects the panel strategy:

      * ``'classic'`` (default) -- replicated partial-pivot panel, the
        bit-exactness A/B + stability baseline.
      * ``'calu'`` -- communication-avoiding tournament pivoting
        (:func:`_calu_panel`): per-grid-row slab LUs, a log-depth playoff
        of candidate pivot blocks, one batched row permutation per panel,
        an unpivoted MXU-friendly panel refactorization, and a
        one-``psum`` row-block solve (:func:`_rowblock_solve_jit`) in
        place of the classic two-round [STAR,VR] dance.  Pivots differ
        from partial pivoting (growth factor bounded by the tournament,
        not by 2^k -- see README "Communication-avoiding LU"); on
        single-row grids (r == 1, incl. 1x1) calu degenerates to classic
        exactly.  The crossover tail finishes with the local classic
        kernel under either strategy.

    ``panel_impl`` (``None`` | ``'xla'`` | ``'pallas'`` | ``'auto'``)
    selects the panel IMPLEMENTATION, orthogonal to the ``panel``
    strategy above: ``'pallas'`` runs the classic replicated panel as
    ONE fused VMEM-resident kernel (``kernels.lu_panel``: pivot search,
    row swaps, column scales, and chunk-blocked trailing updates in a
    single launch; off-TPU it executes under ``interpret=True``), while
    ``None``/``'xla'`` keep the status-quo chunk ladder.  The fused
    kernel's pivot sequence is bit-identical to the ladder's unblocked
    base case (same first-max argmax tie-break, pinned by
    ``tests/kernels``); complex dtypes and panels whose working set
    exceeds the VMEM budget fall back to the XLA twin silently (the
    knob is a performance hint, never a semantics change).  Tree panels
    (``panel='calu'`` tournaments) keep their XLA slab kernels -- the
    knob covers the classic primitives, including the sequential tail.
    ``inners`` optionally overrides the chunk-width ladder
    (``kernels.DEFAULT_INNERS``) for BOTH implementations.

    ``comm_precision`` (``None`` | ``'bf16'`` | ``'int8'``) selects the
    WIRE precision of the schedule's bulk redistributions (panel gathers,
    the U12 row-block transport, the crossover tail gather; the CALU
    row-block psum reduces at bf16 under either mode): payloads are
    block-scale encoded before each collective and decoded after, so
    gathers move 2-4x fewer bytes at identical round counts while all
    local math keeps ``precision``.  Opt-in: ``None`` (default) is
    bit-identical to the unquantized schedule (pinned by tests).  bf16
    wire raises the factor residual to the ~1e-2..1e-3 relative level
    (int8 similar; see README "Quantized collectives") -- pair with
    ``resilience.certified_solve`` for certified answers.

    ``redist_path`` (``None`` | ``'chain'`` | ``'direct'`` | ``'auto'``)
    selects the redistribution ROUTE of the same bulk moves: ``'direct'``
    compiles each dist change into a one-shot collective plan
    (``redist.plan``), ``'auto'`` arbitrates per move via the engine's
    chain-vs-plan cost mirror, ``None``/``'chain'`` keep the factored
    multi-hop chain (bit-identical baseline, pinned by the comm-plan
    goldens).

    ``nb`` / ``lookahead`` / ``crossover`` / ``panel`` /
    ``comm_precision`` / ``redist_path`` accept ``'auto'``: the tuning
    subsystem (``elemental_tpu/tune``) resolves them per (shape, dtype,
    grid, backend) -- measured-cache winner first, analytic cost model
    cold; explicit values always win.  ``panel='auto'`` picks calu on
    multi-row grids and classic on single-row ones (the pivot latency
    term of the cost model).

    ``health`` opts into the resilience subsystem's numerical-health
    guards (``elemental_tpu/resilience``): pass a ``HealthMonitor`` (read
    ``monitor.report()`` afterwards) or ``True`` (report retrievable via
    ``resilience.last_health_report('lu')``).  The monitor rides the same
    tick hook as ``timer`` -- NaN/Inf scans, a growth-factor estimate,
    and near-zero pivot detection at every phase boundary, engine-free.
    ``health=None`` (default) attaches nothing: the zero-overhead
    NULL_HOOK path, pinned by the redist-count goldens.

    ``abft`` opts into checksum-guarded execution with panel-granular
    recovery (``elemental_tpu/resilience/abft.py``, ISSUE 11): pass
    ``True`` (report via ``resilience.last_abft_report('lu')``) or a
    caller-owned ``AbftGuard``.  The guarded path verifies
    Huang-Abraham column-sum invariants after every transport / panel
    factor / trailing update and, on violation, rolls back and
    re-executes ONLY the corrupted panel step (bounded retries, then
    surfaces through ``health_report/v1``).  It forces the CLASSIC
    right-looking schedule on every grid (``lookahead`` / ``crossover``
    / ``panel='calu'`` are ignored: pipelining and tournament pivoting
    do not compose with per-panel transactions).  ``abft=None``
    (default) is the unguarded zero-overhead path, bit-identical to
    before -- pinned by the comm-plan goldens."""
    _check_mcmr(A)
    precision = _hi(precision)
    if any(isinstance(v, str) for v in (nb, lookahead, crossover)) \
            or panel == "auto" or comm_precision == "auto" \
            or redist_path == "auto" or panel_impl == "auto":
        from ..tune.policy import resolve_knobs
        kn = resolve_knobs("lu", gshape=A.gshape, dtype=A.dtype, grid=A.grid,
                           knobs={"nb": nb, "lookahead": lookahead,
                                  "crossover": crossover, "panel": panel,
                                  "panel_impl": panel_impl,
                                  "comm_precision": comm_precision,
                                  "redist_path": redist_path})
        nb, lookahead, crossover = kn["nb"], kn["lookahead"], kn["crossover"]
        panel, comm_precision = kn["panel"], kn["comm_precision"]
        redist_path = kn["redist_path"]
        panel_impl = kn["panel_impl"]
    check_comm_precision(comm_precision)
    rp = redist_path
    plan = _resolve_panel(panel_impl, dtype=A.dtype, inners=inners)
    if abft:
        from ..resilience.abft import abft_lu
        return abft_lu(A, nb=nb, precision=precision,
                       update_precision=update_precision,
                       comm_precision=comm_precision, timer=timer,
                       health=health, abft=abft, plan=plan)
    if panel is None:
        panel = "classic"
    if panel not in ("classic", "calu"):
        raise ValueError(f"lu: unknown panel strategy {panel!r}; "
                         "expected 'classic', 'calu', or 'auto'")
    m, n = A.gshape
    g = A.grid
    tm = _phase_hook("lu", timer)
    hm = None
    if health:
        from ..resilience.health import attach_health
        tm, hm = attach_health("lu", health, tm, scale_from=A)
    if g.size == 1:
        out = _local_lu(A, nb, precision, update_precision, lookahead, tm,
                        plan)
        if hm is not None:
            hm.report()
        return out
    r, c = g.height, g.width
    calu = panel == "calu" and r > 1

    def factor_panel(Ploc, w: int, step: int):
        """One panel under the selected strategy; ticks the tournament
        phase (obs) between pivot selection and the unpivoted refactor.
        The packed result routes through the engine's 'compute' fault
        seam (identity unless a FaultPlan is installed -- ISSUE 9)."""
        if not calu or Ploc.shape[0] <= w:
            Pf, pperm = _panel_dispatch(Ploc, w, precision, plan)
        else:
            with tm.phase("tournament", step) as ph:
                pperm = _tournament_pivots(Ploc, w, r)
                ph.done(pperm)
            Pp = jnp.take(Ploc, pperm, axis=0)
            Pf = _nopiv_panel(Pp, w, precision)
        Pf, = apply_fault("compute", (Pf,))
        return Pf, pperm

    ib = _blocksize(nb, math.lcm(r, c), min(m, n))
    kend = min(m, n)
    perm = jnp.arange(m)
    upd = precision if update_precision is None else update_precision
    xover = (_CROSSOVER if lookahead else 0) if crossover is None \
        else max(int(crossover), 0)
    tm.start()

    def col_up(e):
        # Views must start/end on stride boundaries; a ragged diagonal end
        # (wide matrices, e == m not stride-aligned) is handled by widening
        # every view to a legal boundary and column-masking the writebacks.
        return min(-(-e // c) * c, n)

    cp = comm_precision

    def gather_and_factor(step, src, rows, cols, w):
        # the panel's columns (a view of ``src``; all of it when ``rows``
        # is None) gathered to every device, then the replicated panel
        # factorization
        with tm.phase("panel", step) as ph:
            if rows is not None:
                src = view(src, rows=rows, cols=cols)
            pan = redistribute(src, STAR, STAR, comm_precision=cp, path=rp)
            out = factor_panel(pan.local[:, :w], w, step)
            ph.done(*out)
        return out

    if lookahead:
        w0 = min(ib, kend)
        nxt = gather_and_factor(0, A, (0, m), (0, col_up(w0)), w0)
    for k, s in enumerate(range(0, kend, ib)):
        e = min(s + ib, kend)
        nbw = e - s
        e_up = col_up(e)
        # crossover-to-local: after this step's update the remaining
        # (m-e) x (n-e) trailing block is small enough that ONE gather +
        # a replicated sequential finish beats the per-step collective
        # latency of the remaining steps (e is stride-aligned: e < kend)
        tail = bool(xover) and e < kend and m - e <= xover and n - e <= xover
        if lookahead:
            Pf, pperm = nxt
        else:
            Pf, pperm = gather_and_factor(k, A, (s, m), (s, e_up), nbw)
        with tm.phase("swap", k) as ph:
            perm = perm.at[s:].set(jnp.take(perm[s:], pperm, axis=0))
            # move only the rows the panel permutation displaced (<= 2*nbw)
            # across ALL columns (the panel region is overwritten right
            # after)
            idx, src = _moved_rows(pperm, nbw)
            valid = idx < (m - s)
            A = _apply_swaps_moved(A, idx + s,
                                   jnp.clip(src, 0, m - s - 1) + s, valid)
            ph.done(A)
        # write back the factored panel (rows s..m of cols s..e)
        with tm.phase("panel", k):
            if e_up > e:
                Pf_w = jnp.pad(Pf, ((0, 0), (0, e_up - e)))
            else:
                Pf_w = Pf
            Pf_ss = DistMatrix(Pf_w, (m - s, e_up - s), STAR, STAR, 0, 0, g)
            A = _update_cols_lt(A, redistribute(Pf_ss, MC, MR), (s, m),
                                (s, e_up), e)
        # U12 := L11^{-1} A12 ; A22 -= L21 U12.  The solve runs over the full
        # legal column range (s, n) and the writeback keeps only cols >= e.
        if e >= n:
            continue
        with tm.phase("solve", k) as ph:
            Li11 = _unit_lower_inv(jnp.tril(Pf[:nbw, :], -1)
                                   + jnp.eye(nbw, dtype=Pf.dtype),
                                   nbw, precision)
            if calu:
                # one-psum row-block solve: the contraction over the
                # block's rows distributes across grid rows and a single
                # psum lands [STAR,MR] -- one round instead of the classic
                # all_to_all + all_gather pair below
                U1n_mr = _rowblock_solve_jit(
                    view(A, rows=(s, e), cols=(s, n)), Li11, _hi(precision),
                    "bf16" if cp and quantizable(A.dtype) else None)
            else:
                A1n = redistribute(view(A, rows=(s, e), cols=(s, n)),
                                   STAR, VR, comm_precision=cp, path=rp)
                u1n = _tri_matmul(Li11, A1n.local, "left", _hi(precision)
                                  ).astype(Pf.dtype)
                U1n = DistMatrix(u1n, (nbw, n - s), STAR, VR, 0, 0, g)
                U1n_mr = redistribute(U1n, STAR, MR, comm_precision=cp,
                                      path=rp)
            ph.done(U1n_mr)
        if not lookahead or e >= kend:
            with tm.phase("solve", k):
                A = _update_cols_ge(A, redistribute(U1n_mr, MC, MR), (s, e),
                                    (s, n), e)
            if e < m:      # only non-final panels: e is stride-aligned here
                with tm.phase("update", k) as ph:
                    U12_mr = view(U1n_mr, cols=(e - s, n - s))
                    L21_ss = DistMatrix(Pf[nbw:, :], (m - e, nbw), STAR,
                                        STAR, 0, 0, g)
                    L21_mc = redistribute(L21_ss, MC, STAR)
                    A = local_rank_update(A, L21_mc.local, U12_mr.local,
                                          rows=(e, m), cols=(e, n),
                                          precision=upd)
                    ph.done(A)
            if tail:
                A, perm = _lu_tail(A, perm, e, ib, precision, upd,
                                   lookahead, tm, k, cp, rp, plan)
                break
            continue
        # look-ahead: split the trailing update at the next panel boundary.
        # All operands are captured from the PRE-writeback A, so the panel
        # k+1 factorization and the wide remainder matmul are data-
        # independent and free to overlap.
        e2 = min(e + ib, kend)
        e2_up = col_up(e2)
        with tm.phase("update", k):
            L21_ss = DistMatrix(Pf[nbw:, :], (m - e, nbw), STAR, STAR, 0, 0,
                                g)
            L21_mc = redistribute(L21_ss, MC, STAR)
            U12a = view(U1n_mr, cols=(e - s, e2_up - s))
            A22a = view(A, rows=(e, m), cols=(e, e2_up))
            stripD = A22a.with_local(
                A22a.local - jnp.matmul(L21_mc.local, U12a.local,
                                        precision=upd).astype(A.dtype))
        if not tail:
            # factor panel k+1 from the freshly updated strip (gshape
            # already (m-e, e2_up-e) from the view metadata); skipped when
            # the tail finish below refactors the whole trailing block
            nxt = gather_and_factor(k + 1, stripD, None, None, e2 - e)
        with tm.phase("update", k):
            # (b) wide remainder update, cols >= e2_up
            if e2_up < n:
                U12b = view(U1n_mr, cols=(e2_up - s, n - s))
                A22b = view(A, rows=(e, m), cols=(e2_up, n))
                restD = A22b.with_local(
                    A22b.local - jnp.matmul(L21_mc.local, U12b.local,
                                            precision=upd).astype(A.dtype))
            else:
                restD = None
        # writebacks (U row block, strip, remainder)
        with tm.phase("solve", k):
            A = _update_cols_ge(A, redistribute(U1n_mr, MC, MR), (s, e),
                                (s, n), e)
        with tm.phase("update", k) as ph:
            A = update_view(A, stripD, rows=(e, m), cols=(e, e2_up))
            if restD is not None:
                A = update_view(A, restD, rows=(e, m), cols=(e2_up, n))
            ph.done(A)
        if tail:
            A, perm = _lu_tail(A, perm, e, ib, precision, upd, lookahead,
                               tm, k, cp, rp, plan)
            break
    if hm is not None:
        hm.report()
    return A, perm


def _lu_tail(A: DistMatrix, perm, e: int, ib: int, precision, upd,
             lookahead: bool, tm, k: int, comm_precision=None,
             redist_path=None, plan=None):
    """Crossover-to-local finish of the (fully updated) trailing block.

    One [STAR,STAR] gather of rows/cols >= e, a replicated run of the
    sequential blocked kernel (identical deterministic results on every
    device, like the panel factorization), one storage-level row
    permutation of the already-factored left columns, and a pure-local
    scatter of the factored tail -- the remaining t/nb steps of per-step
    collective latency collapse into a single round trip."""
    m, n = A.gshape
    g = A.grid
    with tm.phase("tail", k) as ph:
        Atail = redistribute(view(A, rows=(e, m), cols=(e, n)), STAR, STAR,
                             comm_precision=comm_precision, path=redist_path)
        at, pt = _local_lu_array(Atail.local, m - e, n - e, ib, precision,
                                 upd, lookahead, plan=plan)
        # the tail's composed row permutation applies to the WHOLE row
        # range (the left factored columns must see the same swaps); cols
        # >= e are overwritten by the factored-tail writeback right after
        A = _apply_swaps_moved(A, jnp.arange(m - e) + e, pt + e,
                               jnp.ones(m - e, dtype=bool))
        At_ss = DistMatrix(at, (m - e, n - e), STAR, STAR, 0, 0, g)
        A = update_view(A, redistribute(At_ss, MC, MR), rows=(e, m),
                        cols=(e, n))
        perm = perm.at[e:].set(jnp.take(perm[e:], pt, axis=0))
        ph.done(A)
    return A, perm


def _blend_update(A: DistMatrix, block: DistMatrix, rows, cols, keep_new):
    from ..blas.level1 import _global_indices
    cur = view(A, rows=rows, cols=cols)
    I, J = _global_indices(cur)
    mask = keep_new(J)[None, :]
    return update_view(A, cur.with_local(jnp.where(mask, block.local, cur.local)),
                       rows=rows, cols=cols)


def _update_cols_lt(A, block, rows, cols, e):
    """Write ``block`` into the view, only at global columns < e."""
    if cols[1] == e:
        return update_view(A, block, rows=rows, cols=cols)
    return _blend_update(A, block, rows, cols, lambda J: J < e - cols[0])


def _update_cols_ge(A, block, rows, cols, e):
    """Write ``block`` into the view, only at global columns >= e."""
    return _blend_update(A, block, rows, cols, lambda J: J >= e - cols[0])


@_scoped("el.lu_solve")
def lu_solve(A: DistMatrix, B: DistMatrix, nb: int | None = None,
             precision=None, panel: str = "classic", info: bool = False,
             health=None):
    """Solve A X = B via LU with partial pivoting (``El::LinearSolve``,
    ``src/lapack_like/solve/LinearSolve.cpp``: LU + SolveAfter).
    ``panel`` selects the factorization's panel strategy (see :func:`lu`);
    the solve-after path is strategy-agnostic -- it only consumes the
    packed factor and the composed permutation.

    ``info=True`` returns ``(X, info)`` where ``info`` is the structured
    singularity signal ``{"singular", "diag_index", "finite"}`` from the
    factor's diagonal (an exactly-singular A surfaces as a zero pivot
    instead of a silently NaN/Inf X -- eager-mode only, like ``timer``);
    ``health`` forwards to :func:`lu` (the resilience guards).  For the
    full residual-certified path use
    ``elemental_tpu.resilience.certified_solve('lu', A, B)``."""
    with jax.named_scope("factor"):
        LU_, perm = lu(A, nb=nb, precision=precision, panel=panel,
                       health=health)
    with jax.named_scope("sweeps"):
        X = lu_solve_after(LU_, perm, B, nb=nb, precision=precision)
    if not info:
        return X
    from ..resilience.health import factor_diag_info
    return X, factor_diag_info("lu", LU_)


def lu_solve_after(LU_: DistMatrix, perm, B: DistMatrix, nb: int | None = None,
                   precision=None) -> DistMatrix:
    """X = U^{-1} L^{-1} P B (``lu::SolveAfter``)."""
    precision = _hi(precision)
    Bp = permute_rows(B, perm)
    Y = trsm("L", "L", "N", LU_, Bp, unit=True, nb=nb, precision=precision)
    return trsm("L", "U", "N", LU_, Y, nb=nb, precision=precision)


def lu_full_pivot(A: DistMatrix, precision=None):
    """LU with COMPLETE pivoting: ``P A Q = L U`` with the pivot the
    largest remaining |entry| each step (``lu::Full``, Elemental
    ``src/lapack_like/factor/LU/Full.hpp``).

    Returns ``(LU, rperm, cperm)`` with the getrf-style packed factor and
    row/column permutations: ``(P A Q)[i, j] = A[rperm[i], cperm[j]]``.

    Runs REPLICATED on the gathered matrix (one jitted fori_loop: the
    per-step global argmax serializes everything -- the reference's
    complete-pivot variant is likewise its slow, maximum-stability path;
    use :func:`lu` (partial pivoting) for speed)."""
    _check_mcmr(A)
    m, n = A.gshape
    kend = min(m, n)
    g = A.grid
    a = redistribute(A, STAR, STAR).local
    ridx = jnp.arange(m)
    cidx = jnp.arange(n)

    def body(j, state):
        a, rp, cp = state
        absa = jnp.abs(a)
        mask = (ridx[:, None] >= j) & (cidx[None, :] >= j)
        cand = jnp.where(mask, absa, -jnp.inf)
        flat = jnp.argmax(cand)
        pi, pj = flat // n, flat % n
        # row swap j <-> pi
        rj, rpv = a[j], a[pi]
        a = a.at[j].set(rpv).at[pi].set(rj)
        rp = rp.at[j].set(rp[pi]).at[pi].set(rp[j])
        # col swap j <-> pj
        cj, cpv = a[:, j], a[:, pj]
        a = a.at[:, j].set(cpv).at[:, pj].set(cj)
        cp = cp.at[j].set(cp[pj]).at[pj].set(cp[j])
        piv = a[j, j]
        safe = jnp.where(piv == 0, 1, piv)
        l = jnp.where(ridx > j, a[:, j] / safe, jnp.zeros_like(a[:, j]))
        a = a.at[:, j].set(jnp.where(ridx > j, l, a[:, j]))
        urow = jnp.where(cidx > j, a[j], jnp.zeros_like(a[j]))
        a = a - jnp.outer(l, urow)
        return a, rp, cp

    a, rp, cp = lax.fori_loop(0, kend, body,
                              (a, jnp.arange(m), jnp.arange(n)))
    LU_ = redistribute(DistMatrix(a, (m, n), STAR, STAR, 0, 0, g), MC, MR)
    return LU_, rp, cp
