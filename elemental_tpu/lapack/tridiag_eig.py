"""Cuppen divide-and-conquer symmetric tridiagonal eigensolver.

The TPU-native replacement for the reference's bundled PMRRR
(``external/pmrrr``, driven from ``src/lapack_like/spectral/HermitianEig.cpp``
via ``herm_tridiag_eig::``): the reference farms the tridiagonal EVP out to
a 15k-LoC MPI+pthreads MRRR code; on TPU the right shape is Cuppen's
divide-and-conquer (LAPACK ``dstedc``'s algorithm), whose O(n^3) work is
eigenvector *matmuls* (MXU) and whose O(n^2) secular-equation work
vectorizes over roots on the VPU.

Design (SURVEY.md §8.1 item 4, VERDICT r3 item 3):

  * **Static shapes, no dynamic deflation.**  LAPACK's ``dlaed2`` deflates
    tiny rank-one weights and rotates away near-equal poles, producing
    data-dependent problem sizes -- hostile to XLA.  Here both cases are
    handled by a bounded PERTURBATION instead: pole gaps are enforced to
    ``>= 8 eps * scale`` (parallel cummax trick) and rank-one weights are
    floored at ``sqrt(eps)``, then the full-size secular problem is solved.
    The computed eigenpairs are EXACT for a tridiagonal within
    ``O(eps * ||T||)`` of the input -- the same backward-error contract as
    deflation, with none of the shape dynamism (the flop saving deflation
    buys on CPUs is irrelevant on the MXU).
  * **mu-anchored bisection.**  Root i of the secular equation
    ``1 + rho sum z_j^2/(d_j - lam) = 0`` is found as ``lam_i = d_i + mu_i``
    by bisecting in ``mu`` over (0, d_{i+1}-d_i): the tiny difference
    ``lam_i - d_i`` is the iterate itself, so eigenvector denominators
    ``(d_j - d_i) - mu_i`` never cancel (the dlaed4 trick).  All roots in
    parallel, memory chunked O(n * chunk).
  * **Gu-Eisenstat reconstruction.**  zhat is recomputed from the computed
    roots via the characteristic-polynomial product formula (log1p-paired
    so partial sums stay bounded), making the eigenvector matrix orthogonal
    to working precision without Gram-Schmidt.
  * **Two-phase batching.**  Subproblems of size <= ``repl_max`` are merged
    REPLICATED and vmap-batched over the subproblem axis ((B, nm, nm)
    arrays, O(n * repl_max) memory); larger merges keep the accumulated
    eigenvector matrix as a block-diagonal [MC,MR] ``DistMatrix`` and do
    the two half-height updates as distributed SUMMA gemms with the secular
    eigenvector matrix V filled TILE-LOCALLY from O(n) replicated vectors
    -- no replicated n x n array ever exists above ``repl_max``.  On a
    grid of several devices whose strides divide the subproblems' order,
    a level's merges are ONE merge in a ``lax.fori_loop`` over the block
    offset (``redist.interior.grain_view`` / ``grain_update``): the
    program holds a merge a level, not a merge a subproblem.

The secular stage runs in float64 when x64 is enabled (CPU mesh tests) and
float32 otherwise (TPU), independent of the storage dtype.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ..core.dist import MC, MR, STAR
from ..core.distmatrix import DistMatrix, zeros as dm_zeros
from ..redist.engine import redistribute
from ..redist.interior import (interior_view, interior_update, grain_view,
                               grain_update)
from ..blas.level1 import index_dependent_fill
from ..blas.level3 import gemm
from ..obs import metrics as _metrics
from ..obs.tracer import NULL_HOOK
from .lu import _hi, _scoped

#: the divide and conquer is ONE jitted program, so nothing times a phase
#: eagerly: its phases only name their ops (``k<level>/leaf``, ``secular``,
#: ``fill``, ``merge``; ``level`` counts merges from the leaves, level 0)
_TM = NULL_HOOK


def _sec_dtype():
    return jnp.float64 if jax.config.jax_enable_x64 else jnp.float32


# ---------------------------------------------------------------------
# secular equation: one merge, all roots in parallel
# ---------------------------------------------------------------------

def _enforce_gaps(ds, eta):
    """Monotone perturbation: ds_i <- max over j<=i of (ds_j + (i-j)*eta),
    guaranteeing ds_{i+1} - ds_i >= eta while moving each entry by at most
    (#violations)*eta.  Parallel via the cummax-of-shifted trick."""
    n = ds.shape[0]
    i = jnp.arange(n, dtype=ds.dtype)
    u = ds - i * eta
    u = lax.associative_scan(jnp.maximum, u)
    return u + i * eta


def _secular(D, z, beta, scale, n_iters: int, chunk: int):
    """Solve eig(D + beta z z^T) with static shapes.

    Returns (lam, perm, ds, tau, aidx, zhat, cninv, flip):
      lam   -- eigenvalues ascending, shape (n,)
      perm  -- argsort of the (possibly negated) pole vector: core row k
               corresponds to original position perm[k]
      ds    -- gap-enforced sorted poles (core domain)
      tau   -- lam_core[i] - ds[aidx[i]]: signed offset from the CLOSER
               interval endpoint (the dlaed4 anchoring -- root i lies in
               (ds[i], ds[i+1]); anchoring at the nearer pole keeps every
               eigenvector denominator ds[k] - lam_i cancellation-free)
      aidx  -- anchor index per root (i or i+1)
      zhat  -- Gu-Eisenstat weights in core row order
      cninv -- 1/||column i||
      flip  -- True where beta < 0: final column c = core column n-1-c,
               final lam = -reverse(core lam)
    All in the secular dtype; the caller maps V entries through
    (perm, flip) when materializing eigenvectors.
    """
    sdt = _sec_dtype()
    eps = jnp.finfo(sdt).eps
    tfloor = 4 * jnp.sqrt(jnp.finfo(sdt).tiny) * jnp.maximum(scale, 1.0)
    D = D.astype(sdt)
    z = z.astype(sdt)
    beta = jnp.asarray(beta, sdt)
    n = D.shape[0]

    flip = beta < 0
    rho = jnp.maximum(jnp.abs(beta), 16 * eps * scale)
    Dw = jnp.where(flip, -D, D)
    perm = jnp.argsort(Dw)
    ds = _enforce_gaps(Dw[perm], 8 * eps * scale)
    zp = z[perm]
    sgn = jnp.where(zp >= 0, 1.0, -1.0).astype(sdt)
    # floor |z| at 2 eps: just enough to keep every secular pole present
    # (no 0/0 in the eigenvector fill); the off-diagonal backward error
    # rho*|dz|*|z_k| stays at eps * ||T||.  A sqrt(eps) floor here costs
    # sqrt(eps)-level residuals -- eigenvector rows of tridiagonals decay
    # exponentially, so tiny z entries are COMMON, not an edge case.
    zs = sgn * jnp.maximum(jnp.abs(zp), 2 * eps)
    z2 = zs * zs
    zn2 = jnp.sum(z2)

    # interval upper widths: gap to next pole; last root in
    # (ds[n-1], ds[n-1] + rho*||z||^2)
    gaps = jnp.concatenate([ds[1:] - ds[:-1],
                            (rho * zn2 * (1 + 4 * eps) + eps * scale)[None]])

    def solve_chunk(s, width):
        idx = s + jnp.arange(width)
        g0 = gaps[idx]
        half = 0.5 * g0
        # anchor choice (dlaed4): f at the interval midpoint; f < 0 means
        # the root is in the upper half -- anchor at the UPPER pole and
        # solve for tau in (-gap/2, 0).  Last root always anchors low.
        diff_lo = ds[None, :] - ds[idx][:, None]       # (C, n): d_j - d_i
        fmid = 1.0 + rho * jnp.sum(
            z2[None, :] / (diff_lo - half[:, None]), axis=1)
        upper = (fmid < 0) & (idx < n - 1)
        aidx = idx + upper
        diff = ds[None, :] - ds[aidx][:, None]         # d_j - d_anchor
        lo = jnp.where(upper, -half, 0.0)
        hi = jnp.where(upper, 0.0, half)

        def body(_, lh):
            lo, hi = lh
            mid = 0.5 * (lo + hi)
            f = 1.0 + rho * jnp.sum(z2[None, :] / (diff - mid[:, None]),
                                    axis=1)
            neg = f < 0
            return jnp.where(neg, mid, lo), jnp.where(neg, hi, mid)

        lo, hi = lax.fori_loop(0, n_iters, body, (lo, hi))
        tau = 0.5 * (lo + hi)
        # Newton polish (clamped to the bisection bracket): restores
        # RELATIVE accuracy for roots tiny compared to their interval,
        # which pure absolute bisection cannot deliver.
        for _ in range(2):
            den = diff - tau[:, None]
            f = 1.0 + rho * jnp.sum(z2[None, :] / den, axis=1)
            fp = rho * jnp.sum(z2[None, :] / (den * den), axis=1)
            t_new = tau - f / fp
            tau = jnp.where((t_new > lo) & (t_new < hi), t_new, tau)
        # keep tau strictly off the anchor pole (else 0/0 downstream)
        tau = jnp.where(upper, jnp.minimum(tau, -tfloor),
                        jnp.maximum(tau, tfloor))
        return tau, aidx

    taus, aidxs = [], []
    c = min(chunk, n)
    for s in range(0, n, c):
        w = min(c, n - s)
        t, a = solve_chunk(s, w)
        taus.append(t)
        aidxs.append(a)
    tau = jnp.concatenate(taus) if len(taus) > 1 else taus[0]
    aidx = jnp.concatenate(aidxs) if len(aidxs) > 1 else aidxs[0]
    off = (ds[aidx] - ds) + tau            # lam_i - ds[i]  (in (0, gap_i))

    # Gu-Eisenstat: zhat_k^2 = prod_i (lam_i - d_k) / (rho prod_{i!=k}
    # (d_i - d_k)), paired per i as log1p(off_i/(d_i - d_k)) so partial
    # sums stay O(1).  Exact special cases: i == k contributes
    # log(off_k); k == aidx_i (upper-anchored neighbor) contributes
    # log(-tau_i) - log(gap_i) since lam_i - d_k = tau_i exactly.
    k_idx = jnp.arange(n)
    acc = jnp.zeros((n,), sdt)
    nrm = jnp.zeros((n,), sdt)                 # column norms^2, core order
    gap_anchor = ds[aidx] - ds                 # gap_i for upper roots, 0 else
    for s in range(0, n, c):
        w = min(c, n - s)
        i_idx = s + jnp.arange(w)
        diff_ki = ds[i_idx][None, :] - ds[:, None]     # (n, C): d_i - d_k
        offi = off[i_idx][None, :]
        is_diag = k_idx[:, None] == i_idx[None, :]
        is_anchor = (k_idx[:, None] == aidx[i_idx][None, :]) & ~is_diag
        safe = jnp.where(is_diag | is_anchor, 1.0, diff_ki)
        generic = jnp.log1p(offi / safe)
        anchor_term = (jnp.log(-tau[i_idx]) -
                       jnp.log(gap_anchor[i_idx]))[None, :] \
            * jnp.ones((n, 1), sdt)
        diag_term = jnp.log(off[i_idx])[None, :] * jnp.ones((n, 1), sdt)
        pair = jnp.where(is_diag, diag_term,
                         jnp.where(is_anchor, anchor_term, generic))
        acc = acc + jnp.sum(pair, axis=1)
    zhat = sgn * jnp.exp(0.5 * (acc - jnp.log(rho)))
    zh2 = zhat * zhat
    for s in range(0, n, c):
        w = min(c, n - s)
        i_idx = s + jnp.arange(w)
        denom = (ds[:, None] - ds[aidx[i_idx]][None, :]) \
            - tau[i_idx][None, :]
        contrib = jnp.sum(zh2[:, None] / (denom * denom), axis=0)
        nrm = nrm.at[i_idx].set(contrib)
    cninv = 1.0 / jnp.sqrt(nrm)

    lam_core = ds + off
    lam = jnp.where(flip, -lam_core[::-1], lam_core)
    return lam, perm, ds, tau, aidx, zhat, cninv, flip


def _v_entries(row_pos, col_pos, perm, ds, tau, aidx, zhat, cninv, flip,
               out_dtype):
    """V[row_pos, col_pos] of the secular eigenvector matrix in ORIGINAL
    row basis and FINAL (ascending-lam) column order, given the core
    quantities from :func:`_secular`.  Shapes broadcast: row_pos (..., 1),
    col_pos (1, ...) or any broadcastable pair of int arrays."""
    n = perm.shape[0]
    invperm = jnp.argsort(perm)
    k = invperm[jnp.clip(row_pos, 0, n - 1)]           # core row of orig row
    col = jnp.where(flip, n - 1 - jnp.clip(col_pos, 0, n - 1),
                    jnp.clip(col_pos, 0, n - 1))
    denom = (ds[k] - ds[aidx[col]]) - tau[col]         # d_k - lam_col, exact
    return (zhat[k] / denom * cninv[col]).astype(out_dtype)


# ---------------------------------------------------------------------
# replicated batched phase
# ---------------------------------------------------------------------

def _merge_replicated(lam1, lam2, Q1, Q2, betas, scale, n_iters, chunk,
                      precision, level: int):
    """One LEVEL of merges on replicated data, batched over the leading
    subproblem axis: returns (lam_new, Q_new) with
    Q_new[b] = blockdiag(Q1[b], Q2[b]) @ V[b].  All matmul work on the MXU.
    The phases are opened AROUND the two ``vmap``s: a scope entered inside
    one reaches the program as ``vmap(k01/secular)``, which no reader of
    the grammar takes for a phase."""
    nm = lam1.shape[1]
    n2 = 2 * nm

    def secular(lam1, lam2, Q1, Q2, beta):
        D = jnp.concatenate([lam1, lam2])
        z = jnp.concatenate([Q1[-1, :], Q2[0, :]])
        lam, perm, ds, tau, aidx, zhat, cninv, flip = _secular(
            D, z, beta, scale, n_iters, chunk)
        rows = jnp.arange(n2)[:, None]
        cols = jnp.arange(n2)[None, :]
        return lam, _v_entries(rows, cols, perm, ds, tau, aidx, zhat, cninv,
                               flip, Q1.dtype)

    def products(Q1, Q2, V):
        # eigenvector accumulation is factor-forming: full f32 accumulation
        # (default bf16-input matmul costs ~1e-3 residuals on TPU)
        top = jnp.matmul(Q1, V[:nm, :], precision=_hi(precision))
        bot = jnp.matmul(Q2, V[nm:, :], precision=_hi(precision))
        return jnp.concatenate([top, bot], axis=0)

    with _TM.phase("secular", level):
        lam, V = jax.vmap(secular)(lam1, lam2, Q1, Q2, betas)
    with _TM.phase("merge", level):
        Q = jax.vmap(products)(Q1, Q2, V)
    return lam.astype(lam1.dtype), Q


def _merge_rows_only(lam1, lam2, fr1, lr1, fr2, lr2, betas, scale, n_iters,
                     chunk, level: int):
    """One level of eigenvalue-only merges, batched like
    :func:`_merge_replicated`: carries just the FIRST and LAST rows of the
    eigenvector matrix (enough to form the next level's z), O(nm^2) work,
    O(nm) state."""
    nm = lam1.shape[1]
    n2 = 2 * nm

    def secular(lam1, lam2, fr1, lr1, fr2, beta):
        D = jnp.concatenate([lam1, lam2])
        z = jnp.concatenate([lr1, fr2])
        lam, perm, ds, tau, aidx, zhat, cninv, flip = _secular(
            D, z, beta, scale, n_iters, chunk)
        rows = jnp.arange(n2)[:, None]
        cols = jnp.arange(n2)[None, :]
        return lam, _v_entries(rows, cols, perm, ds, tau, aidx, zhat, cninv,
                               flip, fr1.dtype)

    def products(fr1, lr1, fr2, lr2, V):
        fr = jnp.concatenate([fr1, jnp.zeros_like(fr2)]) @ V
        lr = jnp.concatenate([jnp.zeros_like(lr1), lr2]) @ V
        return fr, lr

    with _TM.phase("secular", level):
        lam, V = jax.vmap(secular)(lam1, lam2, fr1, lr1, fr2, betas)
    with _TM.phase("merge", level):
        fr, lr = jax.vmap(products)(fr1, lr1, fr2, lr2, V)
    return lam.astype(lam1.dtype), fr, lr


# ---------------------------------------------------------------------
# the hand-off between the two phases
# ---------------------------------------------------------------------

def _place_blocks(Qb, grid) -> DistMatrix:
    """The replicated (B, nm, nm) batch as the block-diagonal [MC,MR]
    matrix of order B * nm: zeros, then each block handed over
    ``[STAR,STAR] -> [MC,MR]`` (a local filter on a grid, nothing on 1x1)
    and placed whole at its diagonal offset.  B dense copies of nm^2
    entries, at any ``nm`` on any grid (:func:`interior_update`'s own offset
    arithmetic): never a function of (i, j) evaluated at all (B nm)^2
    entries, which the compiler makes a gather an ENTRY."""
    B, nm, _ = Qb.shape
    Qd = dm_zeros(B * nm, B * nm, MC, MR, grid, dtype=Qb.dtype)
    for b in range(B):
        _metrics.inc("dc_fill_block")
        block = redistribute(
            DistMatrix(Qb[b], (nm, nm), STAR, STAR, 0, 0, grid), MC, MR)
        Qd = interior_update(Qd, block, (b * nm, b * nm))
    return Qd


# ---------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------

def _plan(n: int, leaf_max: int):
    """(base, levels): npad = base * 2^levels >= n with base in
    (leaf_max/2, leaf_max] so padding never exceeds 2^levels entries."""
    if n <= leaf_max:
        return n, 0
    L = max(0, math.ceil(math.log2(n / leaf_max)))
    base = math.ceil(n / (1 << L))
    return base, L


def _leaf_eigh(d_adj, e_leaf, base: int, B: int):
    """Batched dense EVP of the (B, base, base) leaf blocks; ``e_leaf`` is
    (B, base) with per-leaf interior couplings in columns [0, base-1)."""
    dmat = jax.vmap(jnp.diag)(d_adj.reshape(B, base))
    if base > 1:
        eb = e_leaf[:, :-1]
        idx = jnp.arange(base - 1)
        dmat = dmat.at[:, idx + 1, idx].add(eb)
        dmat = dmat.at[:, idx, idx + 1].add(eb)
    return jnp.linalg.eigh(dmat)


@_scoped("el.tridiag_eig")
def tridiag_eig(d, e, grid=None, vectors: bool = True,
                leaf_max: int = 96, repl_max: int = 512,
                chunk: int = 1024, precision=None):
    """Eigendecomposition of the symmetric tridiagonal T = tridiag(e, d, e).

    Returns ascending ``w`` (replicated, secular dtype cast to d.dtype) and,
    when ``vectors``, the eigenvector matrix as an [MC,MR] ``DistMatrix``
    over ``grid`` (replicated ndarray if ``grid`` is None).

    The scalable replacement for the reference's PMRRR tridiagonal kernel
    (``src/core/imports/pmrrr.cpp``): above ``repl_max`` no replicated
    n x n array is ever materialized.

    The whole driver runs under ONE jit (static plan metadata): eager
    per-op dispatch of its hundreds of small secular-stage ops costs a
    host round trip each.

    Scopes (``el.tridiag_eig/k<level>/...``, level 0 the leaves, then one a
    level of merges): ``leaf`` (the batched dense leaves), ``secular`` (the
    secular equation, the Gu-Eisenstat weights and the fill of V),
    ``fill`` (once, at the last replicated level's number: the hand-off of
    the batch of eigenvector blocks to the [MC,MR] matrix, each block
    placed whole on the diagonal), ``merge`` (the eigenvector products and
    their stores); a rolled level's read ``while/body/closed_call/k<level>/
    ...``.  ``dc_merge`` counts the merges by ``kind`` (``replicated`` |
    ``distributed``), ``dc_fill_block`` the blocks the hand-off places.
    """
    d = jnp.asarray(d)
    e = jnp.asarray(e)
    return _tridiag_eig_jit(d, e, grid, vectors, leaf_max, repl_max,
                            chunk, precision)


@partial(jax.jit, static_argnames=("grid", "vectors", "leaf_max",
                                   "repl_max", "chunk", "precision"))
def _tridiag_eig_jit(d, e, grid, vectors, leaf_max, repl_max, chunk,
                     precision):
    sdt = _sec_dtype()
    n = d.shape[0]
    odt = jnp.result_type(jnp.asarray(d).dtype, jnp.float32)
    d = jnp.asarray(d, sdt)
    e = jnp.asarray(e, sdt)
    n_iters = 62 if sdt == jnp.float64 else 30
    scale = jnp.max(jnp.abs(d)) + 2 * jnp.max(jnp.abs(e)) if n > 1 \
        else jnp.abs(d[0]) + 1.0
    scale = scale + 1e-30

    base, L = _plan(n, leaf_max)
    npad = base << L
    # pad with decoupled sentinel diagonals ABOVE the spectrum so they sort
    # to the tail and slice off exactly
    sent = scale * (3.0 + jnp.arange(npad - n, dtype=sdt))
    dp = jnp.concatenate([d, sent])
    ep = jnp.concatenate([e, jnp.zeros((npad - 1 - (n - 1),), sdt)])

    # pre-apply every split's rank-one diagonal correction: at each interior
    # leaf boundary k (multiple of base), d[k-1] -= e[k-1], d[k] -= e[k-1]
    nblk = npad // base
    bidx = base * jnp.arange(1, nblk)
    beta_all = ep[bidx - 1]
    d_adj = dp.at[bidx - 1].add(-beta_all).at[bidx].add(-beta_all)
    # leaf-interior e, laid out (B, base): column base-1 unused
    e_leaf = jnp.concatenate([ep, jnp.zeros((1,), sdt)]).reshape(nblk, base)

    with _TM.phase("leaf", 0):
        lam, Q = _leaf_eigh(d_adj, e_leaf, base, nblk)
        if vectors:
            Q = Q.astype(odt)    # O(n^3) matmul work runs in storage dtype

    # ---- replicated batched phase ------------------------------------
    B, nm = nblk, base
    level = 0
    if not vectors:
        fr, lr = Q[:, 0, :], Q[:, -1, :]
    while B > 1 and 2 * nm <= max(repl_max, 2 * base):
        level += 1
        _metrics.inc("dc_merge", B // 2, kind="replicated")
        betas = ep[jnp.arange(B // 2) * 2 * nm + nm - 1]
        if vectors:
            lam, Q = _merge_replicated(lam[0::2], lam[1::2], Q[0::2], Q[1::2],
                                       betas, scale, n_iters, chunk, precision,
                                       level)
        else:
            lam, fr, lr = _merge_rows_only(
                lam[0::2], lam[1::2], fr[0::2], lr[0::2], fr[1::2], lr[1::2],
                betas, scale, n_iters, chunk, level)
        B //= 2
        nm *= 2

    if not vectors:
        while B > 1:
            level += 1
            _metrics.inc("dc_merge", B // 2, kind="replicated")
            betas = ep[jnp.arange(B // 2) * 2 * nm + nm - 1]
            lam, fr, lr = _merge_rows_only(
                lam[0::2], lam[1::2], fr[0::2], lr[0::2], fr[1::2], lr[1::2],
                betas, scale, n_iters, chunk, level)
            B //= 2
            nm *= 2
        return lam[0][:n].astype(odt)

    if B == 1:
        w, Z = lam[0], Q[0]
        w, Z = w[:n].astype(odt), Z[:n, :n]
        if grid is None:
            return w, Z
        Zd = redistribute(DistMatrix(Z, (n, n), STAR, STAR, 0, 0, grid),
                          MC, MR)
        return w, Zd

    # ---- distributed phase -------------------------------------------
    if grid is None:
        raise ValueError("tridiag_eig: n exceeds repl_max and no grid given")
    with _TM.phase("fill", level):
        Qd = _place_blocks(Q, grid)
    lam_full = lam.reshape(-1)

    # a level's merges differ in their offset alone.  Where nm is a
    # multiple of both strides the two blocks of a merge are the same
    # local window on every device, so the offset may be a loop counter:
    # the level is ONE compiled merge in a ``fori_loop``, not B / 2 of
    # them (at n = 16384 on 2x2 five bodies for 31 merges).  On other
    # grids the blocks are cut at static offsets, a merge at a time; so
    # they are on ONE device, whose program is left as it was measured.
    on_grain = grid.size > 1 and nm % math.lcm(grid.height, grid.width) == 0

    def merge_of(nm, level):
        """``merge(p, (Qd, lam_full))`` of the level that joins blocks of
        order ``nm``.  A NEW function a level: ``fori_loop`` keeps the
        traced body of a function it has seen for the same carry, and
        every level's carry is the same two arrays."""
        def block(Qd, o):
            if on_grain:
                return grain_view(Qd, (o, o), (nm, nm))
            return interior_view(Qd, (o, o + nm), (o, o + nm))

        def half(lam, o):
            if isinstance(o, int):
                return lam[o:o + nm]
            return lax.dynamic_slice_in_dim(lam, o, nm)

        def store(Qd, Z, i0, j0):
            if on_grain:
                return grain_update(Qd, Z, (i0, j0))
            return interior_update(Qd, Z, (i0, j0))

        def merge(p, state):
            Qd, lam_full = state
            o = p * 2 * nm
            with _TM.phase("secular", level):
                beta = ep[o + nm - 1]
                lam1 = half(lam_full, o)
                lam2 = half(lam_full, o + nm)
                Q1 = block(Qd, o)
                Q2 = block(Qd, o + nm)
                z1 = redistribute(interior_view(Q1, (nm - 1, nm), (0, nm)),
                                  STAR, STAR).local[0]
                z2 = redistribute(interior_view(Q2, (0, 1), (0, nm)),
                                  STAR, STAR).local[0]
                D = jnp.concatenate([lam1, lam2])
                z = jnp.concatenate([z1, z2]).astype(sdt)
                lamn, perm, ds, tau, aidx, zhat, cninv, flip = _secular(
                    D, z, beta, scale, n_iters, chunk)

                def vfill(i, j):
                    return _v_entries(i, j, perm, ds, tau, aidx, zhat,
                                      cninv, flip, odt)

                V = index_dependent_fill(
                    dm_zeros(2 * nm, 2 * nm, MC, MR, grid, dtype=odt), vfill)
            with _TM.phase("merge", level):
                Vtop = interior_view(V, (0, nm), (0, 2 * nm))
                Vbot = interior_view(V, (nm, 2 * nm), (0, 2 * nm))
                Ztop = gemm(Q1, Vtop, precision=_hi(precision))
                Zbot = gemm(Q2, Vbot, precision=_hi(precision))
                Qd = store(Qd, Ztop, o, o)
                Qd = store(Qd, Zbot, o + nm, o)
                lam_full = lax.dynamic_update_slice(lam_full, lamn, (o,))
            return Qd, lam_full
        return merge

    while B > 1:
        level += 1
        merge = merge_of(nm, level)
        _metrics.inc("dc_merge", B // 2, kind="distributed")
        if on_grain and B > 2:
            with _metrics.repeated(B // 2):
                Qd, lam_full = lax.fori_loop(0, B // 2, merge,
                                             (Qd, lam_full))
        else:
            for p in range(B // 2):
                Qd, lam_full = merge(p, (Qd, lam_full))
        B //= 2
        nm *= 2

    w = lam_full[:n].astype(odt)
    Zd = interior_view(Qd, (0, n), (0, n))
    return w, Zd
