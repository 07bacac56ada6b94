"""Spectral layer: Hermitian eigensolvers and the SVD.

Reference: Elemental ``src/lapack_like/spectral/HermitianEig.cpp``
(``El::HermitianEig``: tridiagonalize -> tridiagonal EVP -> back-transform;
upstream solves the tridiagonal problem with bundled PMRRR), ``SVD.cpp``
(``El::SVD``, ``svd::Chan`` tall path), ``HermitianGenDefEig``,
``SkewHermitianEig``, ``HermitianSVD``.

TPU-native redesign (SURVEY.md §8.1 item 4): PMRRR (MPI+pthreads C) has no
TPU analog, so the tridiagonal EVP is solved REDUNDANTLY on every device on
the replicated (d, e) -- the same shape as the reference's older
gather-and-run-LAPACK-redundantly path for bidiagonal SVD -- while all
O(n^3) work (the reduction and the eigenvector back-transform) stays
distributed and matmul-shaped.  The matmul-rich polar-based spectral D&C
(QDWH-eig, PAPERS.md arXiv 2112.09017) lives in :mod:`.funcs` /
:func:`herm_eig` ``approach='qdwh'``.

Subset eigenpairs (``HermitianEigSubset``) select tridiagonal eigenvector
columns BEFORE the back-transform, so a k-subset costs an (n, k) apply-Q.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core.dist import MC, MR, STAR
from ..core.distmatrix import DistMatrix
from ..redist.engine import redistribute, transpose_dist
from ..blas.level3 import _check_mcmr, gemm, trsm, two_sided_trsm
from ..core.view import pad_matrix
from ..redist.interior import interior_view
from ..blas.level1 import diagonal_scale, make_trapezoidal
from .cholesky import cholesky
from .condense import hermitian_tridiag, apply_q_herm_tridiag, _real_dtype
from .lu import permute_cols, _hi, _scoped
from .qr import qr, apply_q
from .tridiag_eig import tridiag_eig
from ..obs import metrics as _metrics

# Above this order the tridiagonal EVP switches from the replicated
# jnp.linalg.eigh fallback to the scalable Cuppen D&C (:mod:`.tridiag_eig`,
# the PMRRR analog) -- no replicated n x n array is materialized past its
# ``repl_max``.  The switchover is tied to repl_max: below it the D&C would
# still run fully replicated (no memory win) at slightly lower accuracy
# than the direct eigh, so there is nothing to gain.
_DC_MIN = 512
_REPL_MAX = 512


def _sym_from_triangle(Ag, uplo: str):
    """Rebuild the full Hermitian matrix from one stored triangle."""
    if uplo.upper().startswith("L"):
        t = jnp.tril(Ag)
        return t + jnp.conj(jnp.tril(t, -1)).T
    t = jnp.triu(Ag)
    return t + jnp.conj(jnp.triu(t, 1)).T


def _subset_slice(w, subset):
    """Resolve a HermitianEigSubset analog to a column slice (host-side).

    ``subset``: None (all), ``('index', il, iu)`` inclusive indices into the
    ascending spectrum, or ``('value', lo, hi)`` selecting the half-open
    interval (lo, hi] -- LAPACK range='V' / ``HermitianEigSubset``
    semantics.  An optional 4th element overrides the searchsorted sides
    (internal; used by the skew translation).
    """
    n = w.shape[0]
    if subset is None:
        return 0, n
    kind = subset[0]
    if kind == "index":
        il, iu = subset[1], subset[2]
        return il, iu + 1
    if kind == "value":
        lo, hi = subset[1], subset[2]
        sides = subset[3] if len(subset) > 3 else ("right", "right")
        wn = np.asarray(w)
        il = int(np.searchsorted(wn, lo, side=sides[0]))
        iu = int(np.searchsorted(wn, hi, side=sides[1]))
        return il, iu
    raise ValueError(f"bad subset {subset!r}")


@_scoped("el.herm_eig")
def herm_eig(A: DistMatrix, uplo: str = "L", vectors: bool = True,
             subset=None, nb: int | None = None, approach: str = "tridiag",
             precision=None, dc_min: int | None = None,
             repl_max: int | None = None):
    """Eigendecomposition of a Hermitian [MC,MR] matrix: ``A = Z diag(w) Z^H``
    (``El::HermitianEig``).  Returns ascending real ``w`` (replicated) and,
    when ``vectors``, the distributed eigenvector matrix ``Z``.

    The driver is ONE traceable program: ``jax.jit(lambda A:
    herm_eig(A, nb=nb), donate_argnums=0)`` compiles the reduction, the
    tridiagonal solve and the back-transform together, with no host round
    trip, for ``subset=None`` and for an ``('index', il, iu)`` subset (a
    static column slice).  A ``('value', lo, hi)`` subset reads ``w`` on the
    host to find its columns, so it cannot be traced: call it eagerly.

    Its ops carry the scopes of the three stages under ``el.herm_eig``
    (grammar in :mod:`elemental_tpu.obs`):
    ``el.hermitian_tridiag/k<panel>/{hemv,panel,update}``,
    ``el.tridiag_eig/k<level>/{leaf,secular,fill,merge}`` (n above ``dc_min``;
    ``fill`` above ``repl_max``) and
    ``el.apply_q_herm_tridiag/k<panel>/apply``; the trace-time counters
    ``herm_tridiag_panel``, ``herm_tridiag_hemv{impl}``,
    ``herm_tridiag_symmetrize``, ``dc_merge{kind}``, ``dc_fill_block`` and
    ``apply_q_panel`` count the panels, which matvec each took (real float32
    on one TPU chip or a square grid of them, the one-pass triangle kernel:
    ``impl=symv`` | ``symv_grid``, no mirror; elsewhere ``impl=mirror``), the
    mirrors of the trailing view (one a panel on that path), the merges
    and the eigenvector blocks placed on the [MC,MR] matrix's diagonal
    between the two kinds of merge.
    """
    _check_mcmr(A)
    n = A.gshape[0]
    if A.gshape != (n, n):
        raise ValueError(f"herm_eig needs square, got {A.gshape}")
    g = A.grid
    rdtype = _real_dtype(A.dtype)
    if n <= 2:
        Ag = _sym_from_triangle(redistribute(A, STAR, STAR).local, uplo)
        w, Z = jnp.linalg.eigh(Ag)
        s, e = _subset_slice(w, subset)
        w = w[s:e].astype(rdtype)
        if not vectors:
            return w
        Zd = redistribute(
            DistMatrix(Z[:, s:e], (n, e - s), STAR, STAR, 0, 0, g), MC, MR)
        return w, Zd
    if approach == "qdwh":
        from .funcs import _qdwh_eig
        return _qdwh_eig(A, uplo, vectors, subset, nb, precision)
    Ap, d, e_, tau = hermitian_tridiag(A, uplo, nb=nb, precision=_hi(precision))
    if dc_min is None:
        dc_min = _DC_MIN
    if repl_max is None:
        repl_max = _REPL_MAX
    if n > dc_min:
        # scalable Cuppen D&C tridiagonal stage (the PMRRR replacement):
        # above repl_max the eigenvector matrix only ever exists [MC,MR]
        if not vectors:
            w = tridiag_eig(d, e_, grid=None, vectors=False,
                            repl_max=repl_max, precision=_hi(precision))
            s, e = _subset_slice(w, subset)
            return w[s:e].astype(rdtype)
        w, ZTd = tridiag_eig(d, e_, grid=g, vectors=True, repl_max=repl_max,
                             precision=_hi(precision))
        s, e = _subset_slice(w, subset)
        w = w[s:e].astype(rdtype)
        if (s, e) != (0, n):
            ZTd = interior_view(ZTd, (0, n), (s, e))
        if ZTd.dtype != A.dtype:
            ZTd = ZTd.astype(A.dtype)
        Z = apply_q_herm_tridiag(Ap, tau, ZTd, orient="N", nb=nb,
                                 precision=_hi(precision))
        return w, Z
    T = (jnp.diag(d) + jnp.diag(e_, -1) + jnp.diag(e_, 1)).astype(rdtype)
    w, ZT = jnp.linalg.eigh(T)            # redundant replicated tridiag solve
    s, e = _subset_slice(w, subset)
    w = w[s:e]
    if not vectors:
        return w
    k = e - s
    ZTd = redistribute(
        DistMatrix(ZT[:, s:e].astype(A.dtype), (n, k), STAR, STAR, 0, 0, g),
        MC, MR)
    Z = apply_q_herm_tridiag(Ap, tau, ZTd, orient="N", nb=nb,
                             precision=_hi(precision))
    return w, Z


def _translate_skew_subset(subset, n: int):
    """Map a subset request on the FINAL ascending imaginary parts
    ``m_j = -w_{n-1-j}`` to one on ``w = eig(iA)`` (ascending)."""
    if subset is None:
        return None
    kind = subset[0]
    if kind == "index":
        il, iu = subset[1], subset[2]
        return ("index", n - 1 - iu, n - 1 - il)
    if kind == "value":
        lo, hi = subset[1], subset[2]
        # m in (lo, hi]  <=>  w = -m in [-hi, -lo)
        return ("value", -hi, -lo, ("left", "left"))
    raise ValueError(f"bad subset {subset!r}")


def skew_herm_eig(A: DistMatrix, uplo: str = "L", vectors: bool = True,
                  subset=None, nb: int | None = None, precision=None,
                  approach: str = "tridiag"):
    """Eigenvalues (purely imaginary, returned as their imaginary parts,
    ascending) of a skew-Hermitian matrix: eig(iA) with a sign flip
    (``El::SkewHermitianEig``)."""
    cdtype = jnp.result_type(A.dtype, jnp.complex64)
    iA = A.with_local((1j * A.local.astype(cdtype)))
    n = A.gshape[0]
    out = herm_eig(iA, uplo, vectors, _translate_skew_subset(subset, n), nb,
                   approach=approach, precision=_hi(precision))
    # eig(A) = -i * eig(iA): imaginary parts are -w; re-sort ascending.
    if not vectors:
        return -out[::-1]
    w, Z = out
    k = Z.gshape[1]
    Zr = permute_cols(Z, jnp.arange(k)[::-1]) if k > 1 else Z
    return (-w)[::-1], Zr


def herm_gen_def_eig(A: DistMatrix, B: DistMatrix, uplo: str = "L",
                     vectors: bool = True, subset=None, nb: int | None = None,
                     precision=None, approach: str = "tridiag"):
    """Generalized definite pencil ``A x = w B x`` with HPD ``B``
    (``El::HermitianGenDefEig``, AXBX form): Cholesky B = L L^H, reduce via
    ``TwoSidedTrsm`` to ``L^-1 A L^-H``, solve, back-substitute
    ``x = L^-H y``."""
    L = cholesky(B, "L", nb=nb, precision=_hi(precision))
    C = two_sided_trsm(uplo, A, L, nb=nb, precision=_hi(precision))
    out = herm_eig(C, uplo, vectors, subset, nb=nb, approach=approach,
                   precision=_hi(precision))
    if not vectors:
        return out
    w, Y = out
    X = trsm("L", "L", "C", L, Y, nb=nb, precision=_hi(precision))
    return w, X


# ---------------------------------------------------------------------
# SVD
# ---------------------------------------------------------------------

def hermitian_svd(A: DistMatrix, uplo: str = "L", vectors: bool = True,
                  nb: int | None = None, precision=None,
                  approach: str = "tridiag"):
    """SVD of a Hermitian matrix via its eigendecomposition
    (``El::HermitianSVD``): s = |w| descending, U = Z*sign(w), V = Z."""
    out = herm_eig(A, uplo, vectors, nb=nb, approach=approach,
                   precision=_hi(precision))
    if not vectors:
        w = out
        return jnp.sort(jnp.abs(w))[::-1]
    w, Z = out
    order = jnp.argsort(-jnp.abs(w))
    s = jnp.abs(w)[order]
    signs = jnp.where(w[order] < 0, -1.0, 1.0).astype(A.dtype)
    V = permute_cols(Z, order)          # distributed column permutation
    d = DistMatrix(signs[:, None], (signs.shape[0], 1), STAR, STAR, 0, 0,
                   A.grid)
    U = diagonal_scale("R", d, V)
    return U, s, V


@_scoped("el.svd")
def svd(A: DistMatrix, vectors: bool = True, approach: str = "auto",
        nb: int | None = None, precision=None, eig_approach: str = "tridiag"):
    """Singular value decomposition ``A = U diag(s) V^H`` (``El::SVD``).

    ``approach``:
      * 'chan'  -- tall path (``svd::Chan``): QR first, SVD of the small R,
        U = Q U_R (the reference's default for m >= 1.5 n).
      * 'polar' -- QDWH polar + Hermitian eigensolve of the factor H
        (matmul-rich, fully distributed; the TPU-paper recipe).
      * 'golub' -- Bidiag + tridiagonal EVP of B^H B + back-transform
        (``svd::GolubReinsch`` analog; see :func:`_svd_golub_kahan`).
      * 'auto'  -- 'chan' when m >= 1.5 n (or the mirrored transpose when
        n >= 1.5 m), else 'polar'.
    ``eig_approach`` is forwarded to the inner :func:`herm_eig` ('qdwh'
    selects the fully-scalable spectral D&C).
    Returns (U, s, V) with s descending (replicated real vector).

    ONE traceable program on every route (``jax.jit(el.svd)``: no host
    read).  With ``nb=None`` the polar route picks each stage's block from
    its shape, the grid and the dtype (``funcs._polar_blocks``); an
    explicit ``nb`` goes to every stage.  Its ops carry ``el.svd/...``:
    ``el.polar/{qdwh_qr<steps>,qdwh_chol<steps>,polar_h}`` (``funcs.polar``),
    the inner ``el.herm_eig`` as it is, ``svd_u`` around ``U = U_p V``;
    the trace-time counter ``svd_route{approach}`` reads the RESOLVED
    approach of each (nested) call.
    """
    _check_mcmr(A)
    m, n = A.gshape
    g = A.grid
    if n > m:
        out = svd(redistribute(transpose_dist(A, conj=True), MC, MR),
                  vectors, approach, nb, precision, eig_approach)
        if not vectors:
            return out
        U, s, V = out
        return V, s, U
    if approach == "auto":
        approach = "chan" if m >= max(int(1.5 * n), n + 1) else "polar"
    if approach == "chan" and m == n:
        approach = "local"
    if approach not in ("chan", "polar", "golub", "local"):
        raise ValueError(f"unknown svd approach {approach!r}")
    _metrics.inc("svd_route", approach=approach)

    if approach == "chan":
        Ap, tau = qr(A, nb=nb, precision=_hi(precision))
        Rd = make_trapezoidal(interior_view(Ap, (0, n), (0, n)), "U")
        out = svd(Rd, vectors, "polar" if n > 128 else "local", nb, precision,
                  eig_approach)
        if not vectors:
            return out
        UR, s, V = out
        # U = Q [UR; 0] -- the row pad is a pure-local storage extension
        U0 = pad_matrix(UR, m, n)
        U = apply_q(Ap, tau, U0, orient="N", nb=nb, precision=_hi(precision))
        return U, s, V

    if approach == "golub":
        return _svd_golub_kahan(A, vectors, nb, precision, eig_approach)

    if approach == "local":
        # replicated fallback for small blocks (the redundant-LAPACK analog)
        Ag = redistribute(A, STAR, STAR).local
        U, s, Vh = jnp.linalg.svd(Ag, full_matrices=False)
        if not vectors:
            return s.astype(_real_dtype(A.dtype))
        Ud = redistribute(DistMatrix(U, (m, n), STAR, STAR, 0, 0, g), MC, MR)
        Vd = redistribute(DistMatrix(jnp.conj(Vh).T, (n, n), STAR, STAR, 0, 0, g),
                          MC, MR)
        return Ud, s.astype(_real_dtype(A.dtype)), Vd

    return _svd_polar(A, vectors, nb, precision, eig_approach)


def _svd_golub_kahan(A: DistMatrix, vectors: bool, nb, precision,
                     eig_approach: str):
    """Golub-Kahan path (``svd::GolubReinsch`` analog): Bidiag, then the
    symmetric tridiagonal EVP of B^H B (with eig_approach='qdwh' this is the
    fully-scalable spectral D&C -- no replicated O(n^2) construct), then
    back-transform U = Q [B V_B S^{-1}; 0], V = P V_B.

    Numerical note: forming B^H B squares the condition number; singular
    values below ~sqrt(eps)*s_max lose relative accuracy (the price of the
    bidiagonal-free tridiagonal solve; use 'polar' when they matter).
    """
    from ..core.view import pad_matrix
    from ..redist.interior import interior_view
    from ..blas.level1 import index_dependent_fill
    from ..core.distmatrix import zeros as dm_zeros
    from .condense import bidiag, apply_p_bidiag
    from .lu import permute_cols
    m, n = A.gshape
    g = A.grid
    rdtype = _real_dtype(A.dtype)
    Ap, d, e, tauq, taup = bidiag(A, nb=nb, precision=_hi(precision))
    epad = jnp.concatenate([jnp.zeros((1,), rdtype), e])      # e_{j-1} at j
    enext = jnp.concatenate([e, jnp.zeros((1,), rdtype)])     # e_j at j
    T0 = dm_zeros(n, n, MC, MR, g, dtype=rdtype)

    def tfill(i, j):
        ic = jnp.clip(i, 0, n - 1)
        jc = jnp.clip(j, 0, n - 1)
        diag = d[ic] ** 2 + epad[ic] ** 2
        # (B^H B)[i, i+1] = d_i e_i ; [i+1, i] its conjugate (real here)
        sup = d[ic] * jnp.take(e, jnp.clip(i, 0, max(n - 2, 0)))
        sub = d[jc] * jnp.take(e, jnp.clip(j, 0, max(n - 2, 0)))
        return jnp.where(i == j, diag,
                         jnp.where(j == i + 1, sup,
                                   jnp.where(i == j + 1, sub, 0.0)))

    T = index_dependent_fill(T0, tfill)
    out = herm_eig(T, "L", vectors, nb=nb, approach=eig_approach,
                   precision=_hi(precision))
    if not vectors:
        w = out
        return jnp.sqrt(jnp.clip(jnp.sort(w)[::-1], 0, None))
    w, Z = out
    order = jnp.argsort(-w)
    s = jnp.sqrt(jnp.clip(w[order], 0, None))
    # cast to A's dtype BEFORE the complex back-transforms (a real-typed VB
    # would silently truncate the reflectors' imaginary parts)
    VB = permute_cols(Z, order).astype(A.dtype)
    # U_B = B V_B S^{-1}: row i of B V_B = d_i VB[i,:] + e_i VB[i+1,:]
    dd = DistMatrix(d[:, None].astype(A.dtype), (n, 1), STAR, STAR, 0, 0, g)
    ee = DistMatrix(enext[:, None].astype(A.dtype), (n, 1), STAR, STAR, 0, 0, g)
    VBshift = pad_matrix(interior_view(VB, (1, n), (0, n)), n, n)
    BV = diagonal_scale("L", dd, VB)
    BV = BV.with_local(BV.local + diagonal_scale("L", ee, VBshift).local)
    sinv = jnp.where(s > 0, 1.0 / jnp.where(s == 0, 1.0, s), 0)
    ds = DistMatrix(sinv[:, None].astype(A.dtype), (n, 1), STAR, STAR, 0, 0, g)
    UB = diagonal_scale("R", ds, BV)
    V = apply_p_bidiag(Ap, taup, VB, orient="N", nb=nb, precision=_hi(precision))
    U = apply_q(Ap, tauq, pad_matrix(UB, m, n), orient="N", nb=nb,
                precision=_hi(precision))
    return U, s, V


def _reversed_cols(V: DistMatrix) -> DistMatrix:
    """The columns in reverse order: a static flip on one device (no
    gather), ``permute_cols`` by a constant order on a grid."""
    if V.grid.size == 1:
        return V.with_local(V.local[:, ::-1])
    return permute_cols(V, jnp.arange(V.gshape[1])[::-1])


def _svd_polar(A: DistMatrix, vectors: bool, nb, precision,
               eig_approach: str):
    # polar path: A = Up H; H = V diag(w) V^H; s = w desc; U = Up V
    from .funcs import polar, _polar_blocks
    m, n = A.gshape
    blocks = _polar_blocks(nb, m, n, A.grid, A.dtype)
    _metrics.inc("polar_block", stage="eig", nb=str(blocks["eig"]))
    Up, H = polar(A, nb=nb, precision=_hi(precision))
    out = herm_eig(H, "L", vectors, nb=blocks["eig"], approach=eig_approach,
                   precision=_hi(precision))
    # H is PSD: w ascending >= 0 (up to rounding); descending order
    if not vectors:
        return jnp.clip(out[::-1], 0, None)
    w, V = out
    Vd = _reversed_cols(V)
    with jax.named_scope("svd_u"):
        U = gemm(Up, Vd, nb=blocks["chol"], precision=_hi(precision))
    return U, jnp.clip(w[::-1], 0, None), Vd
