"""Schur decomposition (spectral divide-and-conquer), triangular
eigenvectors, general eigensolver, and pseudospectra.

Reference: Elemental ``src/lapack_like/spectral/Schur.cpp`` +
``Schur/SDC.hpp`` (``El::schur::SDC``: matrix-sign spectral divide and
conquer with randomized splitting lines), ``TriangEig.cpp``
(``El::TriangEig`` via ``MultiShiftTrsm``), ``Eig.cpp``, and
``Pseudospectra.cpp`` (``El::pspec``: batched inverse-iteration maps over a
shift window).

TPU-native notes:
  * The SDC split is the sign-function analog of funcs._dc_eig: one scaled
    Newton ``sign`` (LU solves -- MXU-shaped) per level, randomized
    range-finder + packed-reflector rotation, interior extract/embed at the
    data-dependent split.  Splitting lines are retried over rotations
    (vertical / horizontal / random angle) like the reference's randomized
    Mobius sweeps.
  * The base case gathers the block and runs the sequential QR algorithm
    redundantly -- EXACTLY the reference's upstream behavior (its
    distributed Schur defers to sequential LAPACK ``hseqr``; SURVEY §3.4).
  * ``triang_eig`` batches all n shifted back-substitutions into one
    multishift sweep where rows >= j of column j's system are replaced by
    identity rows -- the singular shifts (T_jj = lambda_j) never divide.
  * ``pseudospectra`` runs inverse power iteration on (T - z I) for the
    whole shift grid at once through ``multishift_trsm``.

Output convention: COMPLEX Schur form (real input is cast), A = Q T Q^H
with T upper triangular.

Backend note: the device-side arithmetic here is complex64/128, which
XLA:TPU runs by real decomposition (``chip_smoke.py`` solves one
complex64 system on the chip).  Real-input control solvers (Sylvester/
Lyapunov/Riccati) stay in real arithmetic.
"""
from __future__ import annotations

import math

import numpy as np
import jax.numpy as jnp

from ..core.dist import MC, MR, STAR
from ..core.distmatrix import DistMatrix, from_global, to_global
from ..redist.engine import redistribute, transpose_dist
from ..redist.interior import interior_view, interior_update, _blank
from ..blas.level1 import (get_diagonal, shift_diagonal, frobenius_norm,
                           make_trapezoidal, diagonal_scale, _global_indices)
from ..blas.level3 import _check_mcmr, _blocksize, gemm
from .lu import _hi
from .funcs import sign as _matrix_sign
from .qr import qr, apply_q


def _complex_dtype(dtype):
    return jnp.result_type(dtype, jnp.complex64)


def _replicated_schur(A: DistMatrix):
    """Base case: gather + sequential complex QR algorithm, run on host
    (the reference's redundant-hseqr fallback)."""
    try:
        import scipy.linalg
    except ImportError as exc:                      # pragma: no cover
        raise ImportError(
            "schur/eig/pseudospectra need scipy for the sequential "
            "QR-algorithm base case (the reference's hseqr analog); "
            "install scipy or raise `base` is not an option -- every "
            "recursion bottoms out here") from exc
    n = A.gshape[0]
    Ag = np.asarray(to_global(A))
    T, Q = scipy.linalg.schur(Ag, output="complex")
    g = A.grid
    Td = redistribute(DistMatrix(jnp.asarray(T, A.dtype), (n, n), STAR, STAR,
                                 0, 0, g), MC, MR)
    Qd = redistribute(DistMatrix(jnp.asarray(Q, A.dtype), (n, n), STAR, STAR,
                                 0, 0, g), MC, MR)
    return Td, Qd


def _sdc(A: DistMatrix, base: int, nb, precision, seed: int, depth: int = 0):
    """Recursive sign-function SDC; returns (T, Q) with A = Q T Q^H."""
    n = A.gshape[0]
    g = A.grid
    if n <= max(base, 2) or depth > 60:
        return _replicated_schur(A)
    d = get_diagonal(A).local[:, 0]
    rng = np.random.default_rng(0x5DC0 + 31 * seed + depth)
    scale = max(float(frobenius_norm(A)), 1e-30)
    # candidate splitting lines: (shift sigma, rotation theta); the sign of
    # e^{-i theta}(A - sigma I) splits the spectrum across the line through
    # sigma with direction theta + pi/2
    cands = [(complex(float(jnp.median(jnp.real(d)))), 0.0),
             (1j * float(jnp.median(jnp.imag(d))), math.pi / 2)]
    for _ in range(3):
        c = complex(d[rng.integers(n)]) + \
            (rng.normal() + 1j * rng.normal()) * 0.1 * scale / math.sqrt(n)
        cands.append((c, rng.uniform(0, math.pi)))
    split = None
    for sigma, theta in cands:
        try:
            As = shift_diagonal(A, -jnp.asarray(sigma, A.dtype))
            phase = jnp.asarray(np.exp(-1j * theta), A.dtype)
            S = _matrix_sign(As.with_local(phase * As.local), nb=nb,
                             precision=_hi(precision))
        except FloatingPointError:
            continue
        P = shift_diagonal(S.with_local(-0.5 * S.local), 0.5)
        kf = float(jnp.real(jnp.sum(jnp.where(_diag_mask(P), P.local, 0))))
        if not math.isfinite(kf):
            continue        # sign silently filled with NaN/Inf: next line
        k = int(round(kf))
        if not (0 < k < n):
            continue
        G = rng.normal(size=(n, k)) + 1j * rng.normal(size=(n, k))
        Gd = from_global(G.astype(np.dtype(A.dtype)), MC, MR, grid=g)
        Y = gemm(P, Gd, nb=nb, precision=_hi(precision))
        Qp, tau = qr(Y, nb=nb, precision=_hi(precision))
        T1_ = apply_q(Qp, tau, A, orient="C", nb=nb, precision=_hi(precision))
        T2_ = redistribute(transpose_dist(T1_, conj=True), MC, MR)
        T3_ = apply_q(Qp, tau, T2_, orient="C", nb=nb, precision=_hi(precision))
        C = redistribute(transpose_dist(T3_, conj=True), MC, MR)
        # accept only a numerically clean split: the rotated (2,1) block
        # must be negligible (an unconverged sign near the line leaves mass
        # there; the reference's SDC performs the same residual gate)
        A21 = interior_view(C, (k, n), (0, k))
        if float(frobenius_norm(A21)) > 1e-6 * scale:
            continue
        split = (k, Qp, tau, C)
        break
    if split is None:
        return _replicated_schur(A)
    k, Qp, tau, C = split
    A11 = interior_view(C, (0, k), (0, k))
    A22 = interior_view(C, (k, n), (k, n))
    C12 = interior_view(C, (0, k), (k, n))
    Ta, Qa = _sdc(A11, base, nb, precision, 2 * seed + 1, depth + 1)
    Tb, Qb = _sdc(A22, base, nb, precision, 2 * seed + 2, depth + 1)
    T12 = gemm(gemm(Qa, C12, orient_a="C", nb=nb, precision=_hi(precision)), Qb,
               nb=nb, precision=_hi(precision))
    T = _blank(n, n, A)
    T = interior_update(T, Ta, (0, 0))
    T = interior_update(T, T12, (0, k))
    T = interior_update(T, Tb, (k, k))
    BD = _blank(n, n, A)
    BD = interior_update(BD, Qa, (0, 0))
    BD = interior_update(BD, Qb, (k, k))
    Q = apply_q(Qp, tau, BD, orient="N", nb=nb, precision=_hi(precision))
    return make_trapezoidal(T, "U"), Q


def _diag_mask(A: DistMatrix):
    I, J = _global_indices(A)
    return (J[None, :] == I[:, None]) & (I[:, None] < A.gshape[0])


def _global_colnorms(X: DistMatrix, k: int):
    """Column 2-norms in GLOBAL order from the storage array.  Out-of-range
    (padding) storage columns are DROPPED -- clipping first would clobber
    column k-1."""
    ns = jnp.sqrt(jnp.sum(jnp.abs(X.local) ** 2, axis=0))
    _, J = _global_indices(X)
    return jnp.zeros((k,), ns.dtype).at[J].set(ns, mode="drop")


def schur(A: DistMatrix, base: int | None = None, nb: int | None = None,
          precision=None):
    """Complex Schur decomposition A = Q T Q^H (``El::Schur``; SDC path for
    blocks above ``base``).  Returns (T upper triangular, Q unitary)."""
    _check_mcmr(A)
    n = A.gshape[0]
    if A.gshape != (n, n):
        raise ValueError(f"schur needs square, got {A.gshape}")
    cdtype = _complex_dtype(A.dtype)
    Ac = A.astype(cdtype)
    return _sdc(Ac, base if base is not None else 128, nb, precision, seed=1)


def triang_eig(T: DistMatrix, nb: int | None = None, precision=None):
    """Eigenvectors of an upper-triangular T (``El::TriangEig``): one
    batched :func:`multishift_trsm` backward sweep whose diagonal blocks
    are modified per column -- rows >= j become identity rows (so the
    singular shift T_jj - lambda_j never divides) and near-zero pivots are
    clamped to ~eps ||T|| (LAPACK trevc's smin perturbation for repeated /
    defective eigenvalues).  Returns (w = diag(T), V) with unit 2-norm
    columns."""
    from ..blas.level3 import multishift_trsm
    from ..blas.level1 import max_norm
    _check_mcmr(T)
    n = T.gshape[0]
    g = T.grid
    w = get_diagonal(T).local[:, 0]
    rdtype = jnp.zeros((), T.dtype).real.dtype
    smin = jnp.finfo(rdtype).eps * jnp.maximum(max_norm(T), 1e-300) \
        + jnp.finfo(rdtype).tiny

    def hook(M, sg, jg, rowg):
        eye = jnp.eye(M.shape[0], dtype=M.dtype)
        M = jnp.where((rowg >= jg)[:, None], eye, M)
        d_ = jnp.diagonal(M)
        mag = jnp.abs(d_)
        dc = jnp.where(mag < smin,
                       jnp.where(mag == 0, smin,
                                 d_ * (smin / jnp.where(mag == 0, 1, mag))),
                       d_)
        return M + jnp.diag((dc - d_))

    # RHS: e_j per column -- the modified system keeps column j's coupling
    # T[i, j] x[j], so rows i < j see exactly (T - lambda_j)[:j,:j] x = -T[:j, j]
    B = shift_diagonal(_blank(n, n, T), 1)
    X = multishift_trsm("U", "N", T, w, B, nb=nb, precision=_hi(precision),
                        diag_hook=hook)
    # normalize columns to unit 2-norm
    norms = _global_colnorms(X, n)
    inv = jnp.where(norms > 0, 1.0 / jnp.where(norms == 0, 1, norms), 0)
    dinv = DistMatrix(inv[:, None].astype(X.dtype), (n, 1), STAR, STAR, 0, 0, g)
    return w, diagonal_scale("R", dinv, X)


def eig(A: DistMatrix, base: int | None = None, nb: int | None = None,
        precision=None):
    """General (non-Hermitian) eigendecomposition via Schur + TriangEig
    (``El::Eig``): returns (w, V) with A V ~= V diag(w), unit columns."""
    T, Q = schur(A, base=base, nb=nb, precision=_hi(precision))
    w, Vt = triang_eig(T, nb=nb, precision=_hi(precision))
    V = gemm(Q, Vt, nb=nb, precision=_hi(precision))
    # re-normalize (Q is unitary so norms are preserved up to rounding)
    return w, V


def pseudospectra(A: DistMatrix, re_window, im_window, nx: int = 20,
                  ny: int = 20, iters: int = 30, triangular: bool = False,
                  base: int | None = None, nb: int | None = None,
                  precision=None, seed: int = 0, tol: float = 1e-3,
                  check_every: int = 3, deflate: bool = True,
                  quiet_checks: int = 3, snapshot=None):
    """Inverse-norm map est. sigma_min(A - z I) over a 2-D shift window
    (``El::Pseudospectra``): Schur once, then batched inverse power
    iteration on (T - z I)^H (T - z I) through ``multishift_trsm``.

    Deflation (the ``Pseudospectra/{Power,Lanczos}.hpp`` machinery): every
    ``check_every`` sweeps, shifts whose estimate moved by less than
    ``tol`` relatively for ``quiet_checks`` CONSECUTIVE checks are FROZEN
    and removed from the batch (inverse iteration can plateau for a few
    sweeps before converging toward a different value, so a single quiet
    check is not convergence; any loud check resets the shift's counter);
    the active set repacks to the next power-of-two width, so XLA compiles
    at most log2(k) shapes while converged shifts stop costing solves.  The
    ``snapshot`` callable (``SnapshotCtrl`` analog) receives
    ``(sweep, Z, sigmin_so_far)`` after every check for progressive dumps.

    Returns (Z grid (ny, nx) complex, sigmin (ny, nx) float) as host numpy.
    """
    from ..blas.level3 import multishift_trsm
    from ..redist.interior import interior_view
    from .lu import permute_cols
    _check_mcmr(A)
    n = A.gshape[0]
    g = A.grid
    if triangular:
        T = A.astype(_complex_dtype(A.dtype))
    else:
        T, _Q = schur(A, base=base, nb=nb, precision=_hi(precision))
    xs = np.linspace(re_window[0], re_window[1], nx)
    ys = np.linspace(im_window[0], im_window[1], ny)
    Z = xs[None, :] + 1j * ys[:, None]
    all_shifts = Z.reshape(-1)
    k = all_shifts.shape[0]
    rng = np.random.default_rng(seed)
    V0 = rng.normal(size=(n, k)) + 1j * rng.normal(size=(n, k))
    V0 /= np.linalg.norm(V0, axis=0, keepdims=True)
    V = from_global(V0.astype(np.dtype(T.dtype)), MC, MR, grid=g)

    active = np.arange(k)           # global ids of live columns
    ka = k                          # current (padded) batch width
    sh_act = all_shifts.copy()      # length ka, padded with repeats
    est_final = np.zeros(k)
    prev = np.full(k, np.inf)
    quiet = np.zeros(k, dtype=int)      # consecutive quiet checks per shift
    need = max(int(quiet_checks), 1)
    sweep = 0

    def one_sweep(V, shifts_dev, cshifts_dev, width):
        Y = multishift_trsm("U", "N", T, shifts_dev, V, nb=nb,
                            precision=_hi(precision))
        ny_ = _global_colnorms(Y, width)
        dinv = DistMatrix(jnp.where(ny_ > 0, 1 / jnp.where(ny_ == 0, 1, ny_),
                                    0)[:, None].astype(T.dtype),
                          (width, 1), STAR, STAR, 0, 0, g)
        Yn = diagonal_scale("R", dinv, Y)
        U = multishift_trsm("U", "C", T, cshifts_dev, Yn, nb=nb,
                            precision=_hi(precision))
        nu = _global_colnorms(U, width)
        est = jnp.sqrt(ny_ * nu)
        dinv2 = DistMatrix(jnp.where(nu > 0, 1 / jnp.where(nu == 0, 1, nu),
                                     0)[:, None].astype(T.dtype),
                           (width, 1), STAR, STAR, 0, 0, g)
        return diagonal_scale("R", dinv2, U), est

    while sweep < iters and active.size:
        shifts_dev = jnp.asarray(sh_act, T.dtype)
        cshifts_dev = jnp.conj(shifts_dev)
        est = None
        for _ in range(min(check_every, iters - sweep)):
            V, est = one_sweep(V, shifts_dev, cshifts_dev, ka)
            sweep += 1
        estn = np.asarray(est)[: active.size]
        est_final[active] = estn
        rel = np.abs(estn - prev[active]) / np.maximum(np.abs(estn), 1e-300)
        prev[active] = estn
        quiet[active] = np.where(rel < tol, quiet[active] + 1, 0)
        conv = quiet[active] >= need
        if snapshot is not None:
            part = np.where(np.isfinite(est_final) & (est_final > 0),
                            1.0 / np.maximum(est_final, 1e-300), 0.0)
            snapshot(sweep, Z, part.reshape(ny, nx))
        if not (deflate and conv.any()) or sweep >= iters:
            if conv.all():
                break
            continue
        keep = np.nonzero(~conv)[0]
        if keep.size == 0:
            break
        active = active[keep]
        # repack live columns first, pad to the next power of two -- but
        # never GROW the batch (next_pow2(keep) can exceed a non-pow2 ka)
        ka2 = min(ka, 1 << max(int(np.ceil(np.log2(max(keep.size, 1)))), 0))
        pad_ids = np.concatenate(
            [keep, np.repeat(keep[:1], ka2 - keep.size)]) \
            if ka2 > keep.size else keep
        Vp = permute_cols(V, jnp.asarray(
            np.concatenate([pad_ids, np.setdiff1d(np.arange(ka), pad_ids)])
            [:ka]))
        V = interior_view(Vp, (0, n), (0, ka2)) if ka2 < ka else Vp
        sh_act = sh_act[pad_ids]
        ka = ka2
    estn = est_final
    # exactly-singular shifts drive the solves to inf/0: sigma_min = 0 there
    sigmin = np.where(np.isfinite(estn) & (estn > 0), 1.0 / np.maximum(
        estn, 1e-300), 0.0)
    return Z, sigmin.reshape(ny, nx)
