"""Condense layer: reduction to tridiagonal (and Hessenberg) form.

Reference: Elemental ``src/lapack_like/condense/HermitianTridiag/**``
(``El::HermitianTridiag``; blocked panels building a distributed-Hemv W
panel, then a Her2k-style two-sided trailing update -- SURVEY.md §4.5) and
``condense/Hessenberg/**`` (``El::Hessenberg``).

TPU-first design: the reduction panel loop is ONE jitted ``lax.fori_loop``
per panel (LAPACK ``latrd`` semantics); per column the only work against
the trailing matrix is one matvec with the panel's FIXED trailing view,
and it takes one of two forms, chosen once from the input
(:func:`_reads_triangle_once`):

* on ONE TPU chip, real float32: the view stays as stored and the
  one-pass triangle ``symv`` kernel (:mod:`~elemental_tpu.kernels.symv`)
  reads its lower triangle ONCE a column, using every tile it loads for
  ``A_ij x_j`` and for ``A_ij^T x_i`` (the reference's Hemv reads one
  triangle through two accumulators, [MC,STAR] and [MR,STAR]: the same
  two products).  The kernel reads the view through its TRANSPOSE: the
  TPU compiler holds the working matrix column-major, so the transpose is
  the same bytes row-major, the layout a kernel's operand has;
* on a SQUARE grid of TPU chips (``r == c``), real float32: the same, in
  ONE ``shard_map`` a column (:func:`_symv_grid`).  Panels start on
  multiples of ``r``, so chip ``(p, q)`` holds ``A[p + r i, q + r j]`` of
  the zero-aligned view and its stored part is a LOCAL lower triangle; the
  kernel's shard form reads it once, as stored (the compiler holds the
  grid's working shard row-major), for the chip's two products (against
  ``v[q::r]`` for the rows it owns and, transposed, against ``v[p::r]``
  for the columns it owns: the two accumulators), and ONE all-reduce of a
  replicated vector joins the partial results.  The loop keeps its
  vectors in residue-major order there, so cuts and places are blocks;
* everywhere else (non-square grids, where a shard's stored part is a
  trapezoid; complex entries; the CPU):
  once a panel the view is made Hermitian-full (its stored lower triangle
  mirrored above the diagonal: one transpose exchange) and per column a
  single plain :func:`~elemental_tpu.blas.level2.gemv` reads that full
  view, ONE read of the square a column; the second accumulator is paid
  once a panel instead of once a column.

The V/W panels live replicated (n x nb -- small).  The
trailing update ``A22 -= V W^H + W V^H`` is one masked storage matmul on
the MXU (exactly the reference's rank-2k update), so all O(n^3/MXU-friendly)
FLOPs are large matmuls and all latency-bound work is batched into one
compiled loop.

Packing (lower): reflector j has an implicit 1 at row j+1; its tail lives in
``Ap[j+2:, j]``; ``d``/``e`` (real) are returned separately, and also
written to the diagonal/subdiagonal of ``Ap``.  ``uplo`` selects which
triangle of the Hermitian input is READ; the packing is always lower (a
documented deviation from LAPACK's dual packing -- A is Hermitian, so both
read paths factor the same matrix).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from jax.sharding import PartitionSpec

from ..core.compat import shard_map
from ..core.dist import MC, MR, STAR
from ..core.distmatrix import DistMatrix
from ..core.view import view, update_view, round_up
from ..redist.engine import redistribute, transpose_dist
from ..blas.level2 import gemv
from ..blas.level1 import _global_indices
from ..blas.level3 import _blocksize, _check_mcmr, _mask_triangle
from ..obs import metrics as _metrics
from ..obs.tracer import NULL_HOOK, redist_part as _redist_part
from ..kernels.symv import shard_block, symv_lower, symv_lower_shard
from .lu import _update_cols_lt, _hi, _phase_hook, _scoped
from .qr import _larft


def _real_dtype(dtype):
    return jnp.zeros((), dtype).real.dtype


def _wrap_vec(v, grid) -> DistMatrix:
    """Replicated (nt,) vector -> zero-aligned (nt, 1) [MC,MR] DistMatrix."""
    ss = DistMatrix(v[:, None], (v.shape[0], 1), STAR, STAR, 0, 0, grid)
    return redistribute(ss, MC, MR)


def _unwrap_vec(x: DistMatrix):
    return redistribute(x, STAR, STAR).local[:, 0]


def _larfg_at(col, piv, ridx, dtype, at=None):
    """Householder reflector pivoting at row ``piv`` (zeroes rows > piv):
    real beta, H = I - tau v v^H, implicit v[piv] = 1.  ``ridx`` holds the
    row each entry of ``col`` is; ``at`` is where row ``piv`` lies in it
    (``piv`` itself unless the rows are kept in another order)."""
    alpha = col[piv if at is None else at]
    tail2 = jnp.where(ridx > piv, col, 0)
    sigma = jnp.sum(jnp.abs(tail2) ** 2)
    anorm = jnp.sqrt(jnp.abs(alpha) ** 2 + sigma)
    re_a = jnp.real(alpha)
    beta = -jnp.sign(jnp.where(re_a == 0, 1.0, re_a)) * anorm
    degenerate = anorm == 0
    safe_beta = jnp.where(degenerate, 1.0, beta)
    tau = jnp.where(degenerate, 0.0, (safe_beta - alpha) / safe_beta)
    denom = alpha - safe_beta
    safe_denom = jnp.where(denom == 0, 1.0, denom)
    v = jnp.where(ridx > piv, col / safe_denom, 0)
    v = jnp.where(ridx == piv, jnp.ones((), dtype), v)
    return v.astype(dtype), jnp.asarray(tau, dtype), beta


def _larfg_tail(col, jj, ridx, dtype, at=None):
    """Householder reflector zeroing rows > jj+1 of ``col`` (LAPACK larfg:
    real beta, H = I - tau v v^H with implicit v[jj+1] = 1); ``at`` as in
    :func:`_larfg_at`, the place of row ``jj + 1``."""
    return _larfg_at(col, jj + 1, ridx, dtype, at)


def _hermitian_full(Atrail: DistMatrix) -> DistMatrix:
    """The (nt, nt) [MC,MR] trailing view as a FULL Hermitian matrix: the
    stored lower triangle (diagonal included, as stored) where it is, its
    conjugate mirror above.  Only stored entries are read: whatever the
    view holds above the diagonal never reaches the result.  (Not
    :func:`~elemental_tpu.blas.level1.make_symmetric`: that sums two masked
    triangles and, conjugating, makes the diagonal real; ``hemv`` read it
    as stored.)"""
    mirror = redistribute(transpose_dist(Atrail, conj=True), MC, MR)
    return Atrail.with_local(jnp.where(_mask_triangle(Atrail, "L"),
                                       Atrail.local, mirror.local))


def _reads_triangle_once(A: DistMatrix) -> bool:
    """The rule of :func:`hermitian_tridiag`'s matvec, from what the input
    shows: the grid is ONE chip or SQUARE (``r == c``: with panels on
    multiples of ``r`` a chip's shard of the zero-aligned trailing view
    stores a local lower triangle, its diagonal kept or not by the chip's
    place; on ``r != c`` the stored part is a trapezoid), the chip is a TPU
    (true of a described topology too, so a rehearsal takes the path; on
    the CPU the kernel would be interpreted once a column) and the entries
    are real float32 (Mosaic has no complex type; the kernel's products
    and sums are float32 on the VPU, no lower than any ``precision``).
    Then the column loop's matvec is the one-pass triangle ``symv`` kernel
    on the trailing view AS STORED, each chip on its own shard, and nothing
    is mirrored (measured, PERF.md 6, PR 44 and PR 52); everything else
    (non-square grids, complex entries, the CPU) mirrors the view once a
    panel and reads the full square."""
    g = A.grid
    return (g.height == g.width and g.devices[0].platform == "tpu"
            and A.dtype == jnp.float32)


def _hemv_impl(A: DistMatrix) -> str:
    """``herm_tridiag_hemv``'s label: which matvec the column loops take."""
    if not _reads_triangle_once(A):
        return "mirror"
    return "symv" if A.grid.size == 1 else "symv_grid"


def _residue_major(x, r: int, block: int):
    """The rows of a replicated ``(nt, ...)`` array in the STORAGE order of
    an ``r``-cyclic distribution: rows ``p, p + r, ...`` together, residue
    after residue, each residue's rows padded with zero rows to ``block``
    (at least ``ceil(nt / r)``).  Row ``i`` goes to ``(i % r) block + i //
    r``; the two cuts the grid's matvec needs of a vector (``v[p::r]``,
    ``v[q::r]``) are contiguous blocks there."""
    x = jnp.pad(x, [(0, r * block - x.shape[0])] + [(0, 0)] * (x.ndim - 1))
    return jnp.swapaxes(x.reshape((block, r) + x.shape[1:]), 0, 1).reshape(
        x.shape)


def _natural(x, r: int, nt: int):
    """:func:`_residue_major`'s inverse: the ``nt`` rows in their order."""
    block = x.shape[0] // r
    return jnp.swapaxes(x.reshape((r, block) + x.shape[1:]), 0, 1).reshape(
        x.shape)[:nt]


def _symv_grid(Atrail: DistMatrix, v, interpret: bool):
    """``(tril(A) + stril(A)^T) v`` on a square ``r x r`` grid, from ONE
    read of each chip's shard as stored and ONE all-reduce.  ``v`` and the
    result are replicated vectors in residue-major order
    (:func:`_residue_major`, blocks of :func:`~elemental_tpu.kernels.symv.
    shard_block`).  Chip ``(p, q)`` reads blocks ``q`` and ``p`` of ``v``
    (``v[q::r]``, ``v[p::r]``), multiplies its stored part by the one and,
    transposed, by the other (:func:`~elemental_tpu.kernels.symv.
    symv_lower_shard`: the reference's two accumulators, ``[MC,STAR]`` and
    ``[MR,STAR]``) and leaves the two partial results in blocks ``p`` and
    ``q`` of a zero vector, whose sum over all chips IS the product,
    replicated: what the column loop wants next.  Cuts and places are block
    rows the kernel indexes, so a column is the kernel and the sum (the
    reference's ``Contract`` to ``[STAR,STAR]``: ``el.redist.hemv_join``)."""
    g = Atrail.grid
    r, nt = g.height, Atrail.gshape[0]

    def f(a, v):
        part = symv_lower_shard(a, v, lax.axis_index("mc"),
                                lax.axis_index("mr"), stride=r, nt=nt,
                                interpret=interpret)
        with jax.named_scope("el.redist.hemv_join"), _redist_part("wire"):
            return lax.psum(part, ("mc", "mr"))

    return shard_map(f, mesh=g.mesh,
                     in_specs=(Atrail.spec, PartitionSpec()),
                     out_specs=PartitionSpec())(Atrail.local, v)


@partial(jax.jit, static_argnums=(2, 3, 4, 5, 6))
def _tridiag_panel(Atrail: DistMatrix, P, nbw: int, extract_last: bool,
                   precision, step: int, impl: str):
    """latrd: reduce ``nbw`` columns of the trailing matrix.

    ``Atrail`` is the panel's fixed (nt, nt) [MC,MR] trailing view in the
    form its matvec reads (``impl``, :func:`_hemv_impl`): ``symv`` AS
    STORED, the one chip's array whose lower triangle the kernel reads once
    a column (what lies above the diagonal is never read); ``symv_grid`` AS
    STORED too, each chip of a square grid reading its own shard's stored
    part once a column (:func:`_symv_grid`); ``mirror`` made Hermitian-full
    (:func:`_hermitian_full`) and read whole by one ``gemv`` a column.
    ``P`` the replicated panel columns.  Returns (V, W, d, e, tau) with
    V/W the (nt, nbw) replicated reflector/update panels.

    With ``symv_grid`` the loop keeps its vectors (the rows of ``P``, ``V``,
    ``W``) in residue-major order, so that a chip's two cuts of ``v`` and
    the two places of its results are contiguous blocks: ``ridx`` is then
    the map from storage to row, ``at`` the map back, and the panels are
    put in their natural order once, after the loop.

    The column loop names its ops ``k<step>/hemv`` (the one matvec against
    the trailing view) and ``k<step>/panel`` (all the rest), side by
    side: this is one jitted loop, so the names are all a phase is here.
    """
    tm = NULL_HOOK
    nt = Atrail.gshape[0]
    g = Atrail.grid
    dtype = P.dtype
    rdtype = _real_dtype(dtype)
    ridx = jnp.arange(nt)
    at = lambda j: j                                          # noqa: E731
    nd = nbw + 1 if extract_last else nbw
    interpret = g.devices[0].platform != "tpu"
    reordered = impl == "symv_grid"
    if reordered:
        r = g.height
        block, _tile = shard_block(nt, r)
        P = _residue_major(P, r, block)
        # the row each place holds (a place of padding reads nt or more).
        # Held as ONE array the loop reads: computed inside its consumers
        # the map makes every vector of the loop two-dimensional
        place = jnp.arange(r * block)
        ridx = lax.optimization_barrier(place // block + r * (place % block))
        at = lambda j: lax.rem(j, r) * block + lax.div(j, r)  # noqa: E731
    rows = P.shape[0]

    def corrected_col(P, V, W, jj):
        return (P[:, jj] - V @ jnp.conj(W[at(jj), :])
                - W @ jnp.conj(V[at(jj), :]))

    def body(jj, carry):
        V, W, d, e, tau = carry
        with tm.phase("panel", step):
            col = corrected_col(P, V, W, jj)
            d = d.at[jj].set(jnp.real(col[at(jj)]).astype(rdtype))
            v, tau_j, beta = _larfg_tail(
                col, jj, ridx, dtype, at(jj + 1) if reordered else None)
            e = e.at[jj].set(beta.astype(rdtype))
        # the one op per column against the trailing matrix: u = A_trail v,
        # one read of the view (v's leading zeros make this the reference's
        # A22*v on the true subproblem)
        with tm.phase("hemv", step):
            if impl == "symv":
                # compiled wherever the chip is a TPU, a described one too
                u = symv_lower(Atrail.local, v, interpret=interpret)
            elif impl == "symv_grid":
                u = _symv_grid(Atrail, v, interpret)
            else:
                u = _unwrap_vec(gemv(Atrail, _wrap_vec(v, g),
                                     precision=_hi(precision)))
        with tm.phase("panel", step):
            u = u - V @ (jnp.conj(W).T @ v) - W @ (jnp.conj(V).T @ v)
            w = tau_j * u
            w = jnp.where(ridx > jj, w, 0)
            w = w - (0.5 * tau_j * (jnp.conj(w) @ v)) * v
            V = V.at[:, jj].set(v)
            W = W.at[:, jj].set(w.astype(dtype))
            tau = tau.at[jj].set(tau_j)
        return V, W, d, e, tau

    with tm.phase("panel", step):
        init = (jnp.zeros((rows, nbw), dtype), jnp.zeros((rows, nbw), dtype),
                jnp.zeros((nd,), rdtype), jnp.zeros((nbw,), rdtype),
                jnp.zeros((nbw,), dtype))
    # the loop itself stays outside a phase: a scope around it would come
    # first in the path of every op of its body, hemv's too
    V, W, d, e, tau = lax.fori_loop(0, nbw, body, init)
    if extract_last:
        with tm.phase("panel", step):
            col = corrected_col(P, V, W, nbw)
            d = d.at[nbw].set(jnp.real(col[at(nbw)]).astype(rdtype))
    if reordered:
        with tm.phase("panel", step):
            V, W = _natural(V, r, nt), _natural(W, r, nt)
    return V, W, d, e, tau


def _packed_panel(V, d, e, nbw: int, dtype):
    """Assemble the packed panel: diag d, subdiag e, reflector tails below."""
    nt = V.shape[0]
    ridx = jnp.arange(nt)[:, None]
    cidx = jnp.arange(nbw)[None, :]
    packed = jnp.where(ridx >= cidx + 2, V[:, :nbw], 0)
    packed = jnp.where(ridx == cidx, d[:nbw].astype(dtype), packed)
    packed = jnp.where(ridx == cidx + 1, e[:nbw].astype(dtype), packed)
    return packed


@_scoped("el.hermitian_tridiag")
def hermitian_tridiag(A: DistMatrix, uplo: str = "L", nb: int | None = None,
                      precision=None):
    """Reduce a Hermitian [MC,MR] matrix to real tridiagonal form.

    Returns ``(Ap, d, e, tau)``: ``A = Q T Q^H`` with ``T = tridiag(e, d, e)``
    and ``Q = H_0 H_1 ... H_{n-2}`` packed in ``Ap``'s lower triangle
    (``El::HermitianTridiag``).

    Scopes (``el.hermitian_tridiag/k<panel>/...``): ``hemv`` (the matvec's
    operand, made once a panel outside the column loop, and the loop's one
    matvec against it: on one TPU chip the slice of the trailing view as
    stored and the ``el_symv_lower`` kernel; on a square grid of them each
    chip's kernel on its shard of that slice and, under
    ``hemv/shard_map/el.redist.hemv_join``, the one all-reduce that joins
    them; elsewhere the view's mirror into a full Hermitian matrix and a
    ``gemv``), ``panel``
    (the rest of the column loop and the packed panel's store), ``update``
    (the rank-2k trailing update and its hops); ``herm_tridiag_panel`` counts
    the panels, ``herm_tridiag_hemv{impl}`` which matvec each took (``symv`` |
    ``symv_grid`` | ``mirror``: :func:`_hemv_impl`) and
    ``herm_tridiag_symmetrize`` the mirrors (one a panel on the mirror
    path, none on the others).
    """
    _check_mcmr(A)
    n = A.gshape[0]
    if A.gshape != (n, n):
        raise ValueError(f"hermitian_tridiag needs square, got {A.gshape}")
    if uplo.upper().startswith("U"):
        A = redistribute(transpose_dist(A, conj=True), MC, MR)
    g = A.grid
    r, c = g.height, g.width
    dtype = A.dtype
    rdtype = _real_dtype(dtype)
    if n == 0:
        z = jnp.zeros((0,), rdtype)
        return A, z, z, jnp.zeros((0,), dtype)
    if n == 1:
        dd = jnp.real(redistribute(A, STAR, STAR).local[0, 0])[None]
        return A, dd.astype(rdtype), jnp.zeros((0,), rdtype), jnp.zeros((0,), dtype)

    ib = _blocksize(nb, math.lcm(r, c), n)
    kend = n - 1                          # reflector columns 0 .. n-2
    tm = _phase_hook("hermitian_tridiag")
    impl = _hemv_impl(A)
    Ap = A
    d_parts, e_parts, tau_parts = [], [], []
    s = 0
    while s < kend:
        k = s // ib                       # the panel's number
        _metrics.inc("herm_tridiag_panel")
        e_col = min(s + ib, kend)
        nbw = e_col - s
        final = e_col == kend
        wp_end = n if final else min(round_up(e_col, c), n)
        # once a panel, never once a column: the matvec's operand
        _metrics.inc("herm_tridiag_hemv", impl=impl)
        with tm.phase("hemv", k) as ph:
            Atrail = view(Ap, rows=(s, n), cols=(s, n))
            if impl == "mirror":
                _metrics.inc("herm_tridiag_symmetrize")
                Atrail = _hermitian_full(Atrail)
            ph.done(Atrail.local)
        P = redistribute(view(Ap, rows=(s, n), cols=(s, wp_end)), STAR, STAR).local
        # the column loop names its own phases (hemv beside panel)
        V, W, dpan, epan, taupan = _tridiag_panel(Atrail, P, nbw, final,
                                                  precision, k, impl)
        d_parts.append(dpan)
        e_parts.append(epan)
        tau_parts.append(taupan)
        with tm.phase("panel", k) as ph:
            packed = _packed_panel(V, dpan, epan, nbw, dtype)
            if final:
                # last column: its diagonal entry
                nt = n - s
                last = jnp.zeros((nt, 1), dtype).at[nt - 1, 0].set(
                    dpan[nbw].astype(dtype))
                packed = jnp.concatenate([packed, last], axis=1)
                blk = DistMatrix(packed, (nt, nt), STAR, STAR, 0, 0, g)
                Ap = _update_cols_lt(Ap, redistribute(blk, MC, MR), (s, n),
                                     (s, n), n)
            else:
                wpad = wp_end - s - nbw
                if wpad:
                    packed = jnp.pad(packed, ((0, 0), (0, wpad)))
                blk = DistMatrix(packed, (n - s, wp_end - s), STAR, STAR, 0,
                                 0, g)
                Ap = _update_cols_lt(Ap, redistribute(blk, MC, MR), (s, n),
                                     (s, wp_end), e_col)
            ph.done(Ap.local)
        if final:
            break
        # trailing two-sided update: A22 -= V2 W2^H + W2 V2^H (lower triangle)
        with tm.phase("update", k) as ph:
            nt2 = n - e_col
            V2 = V[e_col - s:, :]
            W2 = W[e_col - s:, :]
            V2mc = redistribute(
                DistMatrix(V2, (nt2, nbw), STAR, STAR, 0, 0, g), MC, STAR)
            W2mc = redistribute(
                DistMatrix(W2, (nt2, nbw), STAR, STAR, 0, 0, g), MC, STAR)
            V2Hmr = redistribute(
                DistMatrix(jnp.conj(V2).T, (nbw, nt2), STAR, STAR, 0, 0, g),
                STAR, MR)
            W2Hmr = redistribute(
                DistMatrix(jnp.conj(W2).T, (nbw, nt2), STAR, STAR, 0, 0, g),
                STAR, MR)
            A22 = view(Ap, rows=(e_col, n), cols=(e_col, n))
            upd = (jnp.matmul(V2mc.local, W2Hmr.local,
                              precision=_hi(precision))
                   + jnp.matmul(W2mc.local, V2Hmr.local,
                                precision=_hi(precision)))
            mask = _mask_triangle(A22, "L")
            newloc = jnp.where(mask, A22.local - upd.astype(dtype), A22.local)
            Ap = update_view(Ap, A22.with_local(newloc), rows=(e_col, n),
                             cols=(e_col, n))
            ph.done(Ap.local)
        s = e_col
    d = jnp.concatenate(d_parts)
    e_ = jnp.concatenate(e_parts)
    tau = jnp.concatenate(tau_parts)
    return Ap, d, e_, tau


def _tridiag_v_panel(P, nbw: int):
    """Unit-structured reflector panel from tridiag packing: V[jj+1,jj]=1,
    tails from rows >= jj+2."""
    nt = P.shape[0]
    ridx = jnp.arange(nt)[:, None]
    cidx = jnp.arange(nbw)[None, :]
    V = jnp.where(ridx >= cidx + 2, P[:, :nbw], 0)
    return V + jnp.eye(nt, nbw, k=-1, dtype=P.dtype)


@_scoped("el.apply_q_herm_tridiag")
def apply_q_herm_tridiag(Ap: DistMatrix, tau, B: DistMatrix,
                         orient: str = "N", nb: int | None = None,
                         precision=None) -> DistMatrix:
    """B := Q B ('N') or Q^H B ('C') with Q from :func:`hermitian_tridiag`
    (the back-transform of ``El::HermitianEig``, ``herm_eig::`` +
    ``ApplyPackedReflectors``).  ``nb`` must match the factorization's.
    Each panel's ops are named ``el.apply_q_herm_tridiag/k<panel>/apply``
    (the panel's number in the factorization) and tick ``apply_q_panel``."""
    _check_mcmr(Ap, B)
    n = Ap.gshape[0]
    if B.gshape[0] != n:
        raise ValueError(f"B height {B.gshape[0]} != {n}")
    g = Ap.grid
    r, c = g.height, g.width
    ib = _blocksize(nb, math.lcm(r, c), n)
    kend = n - 1
    tm = _phase_hook("apply_q_herm_tridiag")
    starts = list(range(0, kend, ib))
    if orient == "N":
        starts = starts[::-1]
    for s in starts:
        _metrics.inc("apply_q_panel")
        e_col = min(s + ib, kend)
        nbw = e_col - s
        wp_end = n if e_col == kend else min(round_up(e_col, c), n)
        with tm.phase("apply", s // ib) as ph:
            P = redistribute(view(Ap, rows=(s, n), cols=(s, wp_end)),
                             STAR, STAR).local
            V = _tridiag_v_panel(P, nbw)
            T = _larft(V, tau[s:e_col])
            Tm = jnp.conj(T).T if orient == "C" else T
            V_mc = redistribute(
                DistMatrix(V, (n - s, nbw), STAR, STAR, 0, 0, g), MC, STAR)
            B2 = view(B, rows=(s, n))
            Wl = jnp.matmul(jnp.conj(V_mc.local).T, B2.local,
                            precision=_hi(precision))
            Wl = jnp.matmul(Tm, Wl, precision=_hi(precision))
            upd = jnp.matmul(V_mc.local, Wl, precision=_hi(precision))
            B = update_view(B, B2.with_local(B2.local - upd.astype(B.dtype)),
                            rows=(s, n))
            ph.done(B.local)
    return B


# ---------------------------------------------------------------------
# Bidiagonal reduction (the SVD condense step)
# ---------------------------------------------------------------------

@partial(jax.jit, static_argnums=(3, 4))
def _bidiag_panel(Atrail: DistMatrix, Pc, Pr, nbw: int, precision):
    """labrd: reduce ``nbw`` columns AND rows of the (mt, nt) trailing view.

    ``Pc``/``Pr``: replicated panel columns (mt, nbw) / rows (nbw, nt) at
    panel start.  The running matrix is ``A0 - U Y^H - X V^H``; per column
    the two distributed ops are one ``gemv^H`` (building Y) and one ``gemv``
    (building X) against the FIXED trailing view -- the reference's
    ``bidiag::PanelBidiag`` distributed products."""
    mt, nt = Atrail.gshape
    g = Atrail.grid
    dtype = Pc.dtype
    rdtype = _real_dtype(dtype)
    ridx = jnp.arange(mt)
    cidx = jnp.arange(nt)

    def body(j, carry):
        U, Y, V, X, d, e, tauq, taup = carry
        # current column j
        col = Pc[:, j] - U @ jnp.conj(Y[j, :]) - X @ jnp.conj(V[j, :])
        u, tq, beta = _larfg_at(col, j, ridx, dtype)
        d = d.at[j].set(beta.astype(rdtype))
        # zlarfg: H^H x = beta e, so the left update A <- H^H A is
        # A - u y^H with y = tq * A_cur^H u
        base = _unwrap_vec(gemv(Atrail, _wrap_vec(u, g), orient="C",
                                precision=_hi(precision)))
        y = base - Y @ (jnp.conj(U).T @ u) - V @ (jnp.conj(X).T @ u)
        y = (tq * y).astype(dtype)
        U = U.at[:, j].set(u)
        Y = Y.at[:, j].set(y)
        tauq = tauq.at[j].set(tq)
        # current row j (after the left update): right reflector at col j+1
        row = Pr[j, :] - U[j, :] @ jnp.conj(Y).T - X[j, :] @ jnp.conj(V).T
        do_right = j + 1 < nt
        rbar = jnp.conj(row)
        v, tp, betar = _larfg_at(rbar, jnp.minimum(j + 1, nt - 1), cidx, dtype)
        v = jnp.where(do_right, v, jnp.zeros_like(v))
        tp = jnp.where(do_right, tp, 0)
        e = e.at[j].set(jnp.where(do_right, betar, 0).astype(rdtype))
        # right update A <- A G with G = I - tp v v^H: x = tp * A_cur v
        basex = _unwrap_vec(gemv(Atrail, _wrap_vec(v, g), orient="N",
                                 precision=_hi(precision)))
        x = basex - U @ (jnp.conj(Y).T @ v) - X @ (jnp.conj(V).T @ v)
        x = (tp * x).astype(dtype)
        V = V.at[:, j].set(v)
        X = X.at[:, j].set(x)
        taup = taup.at[j].set(tp)
        return U, Y, V, X, d, e, tauq, taup

    init = (jnp.zeros((mt, nbw), dtype), jnp.zeros((nt, nbw), dtype),
            jnp.zeros((nt, nbw), dtype), jnp.zeros((mt, nbw), dtype),
            jnp.zeros((nbw,), rdtype), jnp.zeros((nbw,), rdtype),
            jnp.zeros((nbw,), dtype), jnp.zeros((nbw,), dtype))
    return lax.fori_loop(0, nbw, body, init)


def bidiag(A: DistMatrix, nb: int | None = None, precision=None):
    """Reduce a tall/square [MC,MR] matrix (m >= n) to upper bidiagonal
    form ``A = Q B P^H`` (``El::Bidiag``, ``src/lapack_like/condense/
    Bidiag/**``).

    Returns ``(Ap, d, e, tauq, taup)``: ``d`` the diagonal, ``e`` the
    superdiagonal (length n-1); left reflectors packed below the diagonal
    of ``Ap`` (unit at row j -- geqrf layout, so :func:`.qr.apply_q`
    applies Q); right reflector j's tail stored in ROW j at columns
    >= j+2 (unit at column j+1), applied by :func:`apply_p_bidiag`."""
    _check_mcmr(A)
    m, n = A.gshape
    if m < n:
        raise ValueError("bidiag requires m >= n (transpose the input)")
    g = A.grid
    r, c = g.height, g.width
    dtype = A.dtype
    rdtype = _real_dtype(dtype)
    if n == 0:
        z = jnp.zeros((0,), rdtype)
        return A, z, z, jnp.zeros((0,), dtype), jnp.zeros((0,), dtype)
    grain = math.lcm(r, c)
    ib = _blocksize(nb, grain, n)
    Ap = A
    d_parts, e_parts, tq_parts, tp_parts = [], [], [], []
    for s in range(0, n, ib):
        e_col = min(s + ib, n)
        nbw = e_col - s
        Atrail = view(Ap, rows=(s, m), cols=(s, n))
        ce_up = min(round_up(e_col, c), n)
        re_up = min(round_up(e_col, r), m)
        Pc = redistribute(view(Ap, rows=(s, m), cols=(s, ce_up)),
                          STAR, STAR).local[:, :nbw]
        Pr = redistribute(view(Ap, rows=(s, re_up), cols=(s, n)),
                          STAR, STAR).local[:nbw, :]
        U, Y, V, X, dpan, epan, tq, tp = _bidiag_panel(Atrail, Pc, Pr, nbw,
                                                       precision)
        d_parts.append(dpan)
        e_parts.append(epan)
        tq_parts.append(tq)
        tp_parts.append(tp)
        # packed panel columns: u tails below diag, d on diag, e on superdiag
        mt, nt = m - s, n - s
        rl = jnp.arange(mt)[:, None]
        cl = jnp.arange(nbw)[None, :]
        packedc = jnp.where(rl > cl, U[:, :nbw], 0)
        packedc = jnp.where(rl == cl, dpan[None, :nbw].astype(dtype)
                            * jnp.ones((mt, 1), dtype), packedc)
        esup = jnp.concatenate([jnp.zeros((1,), rdtype), epan[:nbw]])
        packedc = jnp.where(rl == cl - 1,
                            esup[None, jnp.arange(nbw)].astype(dtype)
                            * jnp.ones((mt, 1), dtype), packedc)
        # in-panel right-reflector tails: entry (i, jc) with i <= jc-2 holds
        # v_i[jc] (row-stored packing restricted to the panel's columns)
        VT = jnp.pad(V.T[:nbw, :nbw], ((0, max(mt - nbw, 0)), (0, 0)))[:mt, :]
        packedc = jnp.where(rl + 2 <= cl, VT, packedc)
        if ce_up > e_col:
            packedc = jnp.pad(packedc, ((0, 0), (0, ce_up - e_col)))
        blk = DistMatrix(packedc, (mt, ce_up - s), STAR, STAR, 0, 0, g)
        Ap = _update_cols_lt(Ap, redistribute(blk, MC, MR), (s, m),
                             (s, ce_up), e_col)
        # packed panel rows: v tails right of superdiag, e on superdiag
        rl2 = jnp.arange(nbw)[:, None]
        cl2 = jnp.arange(nt)[None, :]
        packedr = jnp.where(cl2 > rl2 + 1, V.T[:nbw, :], 0)
        packedr = jnp.where(cl2 == rl2 + 1,
                            epan[:nbw, None].astype(dtype)
                            * jnp.ones((1, nt), dtype), packedr)
        if re_up > e_col:
            packedr = jnp.pad(packedr, ((0, re_up - e_col), (0, 0)))
        blkr = DistMatrix(packedr, (re_up - s, nt), STAR, STAR, 0, 0, g)
        cur = view(Ap, rows=(s, re_up), cols=(s, n))
        I2, J2 = _global_indices(cur)
        # rows < nbw, columns >= e_col only: the diag/superdiag and in-panel
        # tails are owned by the column write above
        keep = (I2 < nbw)[:, None] & (J2 >= (e_col - s))[None, :]
        merged = jnp.where(keep, redistribute(blkr, MC, MR).local, cur.local)
        Ap = update_view(Ap, cur.with_local(merged), rows=(s, re_up),
                         cols=(s, n))
        if e_col == n:
            break
        # trailing update: A22 -= U2 Y2^H + X2 V2^H
        U2 = U[nbw:, :]
        X2 = X[nbw:, :]
        Y2 = Y[nbw:, :]
        V2 = V[nbw:, :]
        mt2, nt2 = m - e_col, n - e_col
        U2mc = redistribute(DistMatrix(U2, (mt2, nbw), STAR, STAR, 0, 0, g),
                            MC, STAR)
        X2mc = redistribute(DistMatrix(X2, (mt2, nbw), STAR, STAR, 0, 0, g),
                            MC, STAR)
        Y2Hmr = redistribute(DistMatrix(jnp.conj(Y2).T, (nbw, nt2), STAR,
                                        STAR, 0, 0, g), STAR, MR)
        V2Hmr = redistribute(DistMatrix(jnp.conj(V2).T, (nbw, nt2), STAR,
                                        STAR, 0, 0, g), STAR, MR)
        A22 = view(Ap, rows=(e_col, m), cols=(e_col, n))
        upd = (jnp.matmul(U2mc.local, Y2Hmr.local, precision=_hi(precision))
               + jnp.matmul(X2mc.local, V2Hmr.local, precision=_hi(precision)))
        Ap = update_view(Ap, A22.with_local(A22.local - upd.astype(dtype)),
                         rows=(e_col, m), cols=(e_col, n))
    d = jnp.concatenate(d_parts)[:n]
    e_ = jnp.concatenate(e_parts)[:n - 1] if n > 1 else jnp.zeros((0,), rdtype)
    tauq = jnp.concatenate(tq_parts)[:n]
    taup = jnp.concatenate(tp_parts)[:max(n - 1, 0)]
    return Ap, d, e_, tauq, taup


def apply_p_bidiag(Ap: DistMatrix, taup, B: DistMatrix, orient: str = "N",
                   nb: int | None = None, precision=None) -> DistMatrix:
    """B := P B ('N') or P^H B ('C') with P = G_0 G_1 ... G_{n-2} the
    right-reflector product from :func:`bidiag` (G_j = I - taup_j
    v_j v_j^H, v_j unit at position j+1)."""
    _check_mcmr(Ap, B)
    n = Ap.gshape[1]
    if B.gshape[0] != n:
        raise ValueError(f"B height {B.gshape[0]} != {n}")
    g = Ap.grid
    r, c = g.height, g.width
    ib = _blocksize(nb, math.lcm(r, c), n)
    kend = max(n - 1, 0)
    starts = list(range(0, kend, ib))
    if orient == "N":
        starts = starts[::-1]
    for s in starts:
        e_col = min(s + ib, kend)
        nbw = e_col - s
        re_up = min(round_up(e_col, r), Ap.gshape[0])
        Prow = redistribute(view(Ap, rows=(s, re_up), cols=(s, n)),
                            STAR, STAR).local[:nbw, :]
        # V panel: v_j tails from row j at cols >= j+2 (unit at j+1)
        nt = n - s
        rl = jnp.arange(nt)[:, None]
        cl = jnp.arange(nbw)[None, :]
        V = jnp.where(rl >= cl + 2, Prow.T[:nt, :nbw], 0)
        V = V + jnp.eye(nt, nbw, k=-1, dtype=Prow.dtype)
        T = _larft(V, taup[s:e_col])
        Tm = jnp.conj(T).T if orient == "C" else T
        V_mc = redistribute(
            DistMatrix(V, (nt, nbw), STAR, STAR, 0, 0, g), MC, STAR)
        B2 = view(B, rows=(s, n))
        Wl = jnp.matmul(jnp.conj(V_mc.local).T, B2.local, precision=_hi(precision))
        Wl = jnp.matmul(Tm, Wl, precision=_hi(precision))
        upd = jnp.matmul(V_mc.local, Wl, precision=_hi(precision))
        B = update_view(B, B2.with_local(B2.local - upd.astype(B.dtype)),
                        rows=(s, n))
    return B


# ---------------------------------------------------------------------
# Hessenberg reduction (for Schur / pseudospectra)
# ---------------------------------------------------------------------

def hessenberg(A: DistMatrix, nb: int | None = None, precision=None):
    """Reduce A to upper Hessenberg form: A = Q H Q^H
    (``El::Hessenberg``, lower/'L' reflector convention).

    Returns ``(H, Q_packed, tau)`` where ``H`` is the [MC,MR] Hessenberg
    matrix and ``Q_packed``/``tau`` hold the reflectors (same packing as
    :func:`hermitian_tridiag`).

    v1 is unblocked at panel granularity (per-column distributed gemv +
    per-panel rank-2k trailing updates come with the blocked Schur work);
    correctness-first -- the spectral layer's Schur path is the consumer.
    """
    _check_mcmr(A)
    n = A.gshape[0]
    if A.gshape != (n, n):
        raise ValueError(f"hessenberg needs square, got {A.gshape}")
    g = A.grid
    dtype = A.dtype
    if n <= 2:
        return A, A, jnp.zeros((max(n - 1, 0),), dtype)
    # v1: replicated reduction (correctness path; the distributed blocked
    # version follows the tridiag pattern once Schur lands)
    Ag = redistribute(A, STAR, STAR).local
    ridx = jnp.arange(n)

    def body(jj, carry):
        Ag, Vp, tau = carry
        col = Ag[:, jj]
        v, tau_j, _ = _larfg_tail(col, jj, ridx, dtype)
        # A := H^H A H, H = I - tau v v^H
        w = jnp.conj(tau_j) * (jnp.conj(v) @ Ag)
        Ag = Ag - jnp.outer(v, w)
        u = Ag @ (tau_j * v)
        Ag = Ag - jnp.outer(u, jnp.conj(v))
        Vp = Vp.at[:, jj].set(v)
        tau = tau.at[jj].set(tau_j)
        return Ag, Vp, tau

    Ag, Vp, tau = lax.fori_loop(
        0, n - 1, body,
        (Ag, jnp.zeros((n, n - 1), dtype), jnp.zeros((n - 1,), dtype)))
    # zero below the first subdiagonal (numerical dust from the loop)
    Hloc = jnp.where(jnp.arange(n)[:, None] > jnp.arange(n)[None, :] + 1, 0, Ag)
    H = redistribute(DistMatrix(Hloc, (n, n), STAR, STAR, 0, 0, g), MC, MR)
    packed = jnp.where(jnp.arange(n)[:, None] >= jnp.arange(n - 1)[None, :] + 2,
                       Vp, 0)
    ridx2 = jnp.arange(n)[:, None]
    cidx2 = jnp.arange(n - 1)[None, :]
    packed = jnp.where(ridx2 == cidx2 + 1, Hloc[:, :n - 1], packed)
    packed = jnp.where(ridx2 == cidx2, Hloc[:, :n - 1], packed)
    Qp = redistribute(DistMatrix(packed, (n, n - 1), STAR, STAR, 0, 0, g), MC, MR)
    return H, Qp, tau


def apply_q_hessenberg(Qp: DistMatrix, tau, B: DistMatrix, orient: str = "N",
                       precision=None) -> DistMatrix:
    """B := Q B / Q^H B with Q from :func:`hessenberg` (packing as tridiag)."""
    n = B.gshape[0]
    g = B.grid
    P = redistribute(Qp, STAR, STAR).local
    nref = tau.shape[0]
    V = _tridiag_v_panel(jnp.pad(P, ((0, 0), (0, max(0, n - P.shape[1])))), nref)
    T = _larft(V, tau)
    Tm = jnp.conj(T).T if orient == "C" else T
    V_mc = redistribute(DistMatrix(V, (n, nref), STAR, STAR, 0, 0, g), MC, STAR)
    Wl = jnp.matmul(jnp.conj(V_mc.local).T, B.local, precision=_hi(precision))
    Wl = jnp.matmul(Tm, Wl, precision=_hi(precision))
    upd = jnp.matmul(V_mc.local, Wl, precision=_hi(precision))
    return B.with_local(B.local - upd.astype(B.dtype))
