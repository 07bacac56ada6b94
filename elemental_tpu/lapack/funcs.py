"""Matrix functions: QDWH polar, matrix sign, inverses, pseudoinverse,
square roots, and the polar-based spectral divide-and-conquer eigensolver.

Reference: Elemental ``src/lapack_like/funcs/`` -- ``Sign.cpp`` (``El::Sign``,
Newton iteration with scaling), ``Polar`` (``polar::QDWH``),
``Inverse/**`` (``El::Inverse`` via LU, ``TriangularInverse``,
``HPDInverse``), ``Pseudoinverse.cpp``, ``SquareRoot.cpp`` (Newton).

TPU-native design (SURVEY.md §8.1 item 4, PAPERS.md arXiv 2112.09017): the
QDWH iteration is the workhorse -- every step is a Cholesky or QR plus a few
large matmuls, i.e. pure MXU food -- and it REPLACES the reference's
bundled PMRRR: :func:`_qdwh_eig` splits the spectrum recursively with polar
projectors, extracting the deflated blocks at data-dependent offsets with
:mod:`..redist.interior` (one ppermute per dim -- no replicated construct
anywhere, unlike the tridiagonal fallback path in :mod:`.spectral`).

The scalar QDWH parameter recurrence (a, b, c, l) is data-INdependent given
the initial lower bound, so it is precomputed on the host and the iteration
count is static per (alpha, l0) -- jit-friendly, no data-dependent control
flow on device.
"""
from __future__ import annotations

import itertools
import math

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..core.dist import MC, MR, STAR
from ..core.distmatrix import DistMatrix
from ..redist.engine import redistribute, transpose_dist
from ..redist.interior import interior_view, interior_update, _blank
from ..blas.level1 import (frobenius_norm, one_norm, infinity_norm,
                           shift_diagonal, get_diagonal, make_symmetric,
                           trace as dm_trace)
from ..blas.level3 import _check_mcmr, gemm, trsm, herk
from ..obs import metrics as _metrics
from ..obs.tracer import scoped as _scoped
from ..tune.policy import stage_blocksize
from .cholesky import cholesky, hpd_solve
from .lu import lu_solve, _hi
from .qr import qr, apply_q, _stack_qr_thin_q


def _real_dtype(dtype):
    return jnp.zeros((), dtype).real.dtype


def _eps_of(dtype) -> float:
    return float(jnp.finfo(_real_dtype(dtype)).eps)


def _identity_like(A: DistMatrix, m: int, n: int | None = None) -> DistMatrix:
    out = _blank(m, n or m, A)
    return shift_diagonal(out, 1)


def _hermitianize(H: DistMatrix) -> DistMatrix:
    Ht = redistribute(transpose_dist(H, conj=True), MC, MR)
    return H.with_local(0.5 * (H.local + Ht.local))


# ---------------------------------------------------------------------
# QDWH polar decomposition
# ---------------------------------------------------------------------

def _qdwh_schedule(l0: float, tol: float, maxiter: int = 32):
    """Host-side (a, b, c) parameter schedule from the lower bound l0.

    The dynamically-weighted Halley parameters (Nakatsukasa-Bai-Gygi /
    Nakatsukasa-Higham); l_{k+1} = l_k (a + b l^2) / (1 + c l^2) is
    data-independent, so the whole schedule is static."""
    params = []
    l = float(l0)
    while 1.0 - l > tol and len(params) < maxiter:
        l2 = l * l
        dd = (4.0 * (1.0 - l2) / (l2 * l2)) ** (1.0 / 3.0)
        sqd = math.sqrt(1.0 + dd)
        a = sqd + 0.5 * math.sqrt(
            max(8.0 - 4.0 * dd + 8.0 * (2.0 - l2) / (l2 * sqd), 0.0))
        b = (a - 1.0) ** 2 / 4.0
        c = a + b - 1.0
        params.append((a, b, c))
        l = l * (a + b * l2) / (1.0 + c * l2)
    # two pure-Halley cleanup steps (cubic convergence at the fixed point)
    params.append((3.0, 1.0, 3.0))
    params.append((3.0, 1.0, 3.0))
    return params


def _qdwh_step_chol(X: DistMatrix, c, keep, gain, blocks,
                    precision) -> DistMatrix:
    """Cholesky-variant step (safe once c is moderate): Z = I + c X^H X,
    Z = W W^H, X' = keep X + gain X W^{-H} W^{-1} with keep = b / c,
    gain = a - b / c."""
    nb = blocks["chol"]
    Z = herk("L", X, alpha=c, orient="C", nb=nb, precision=_hi(precision))
    Z = shift_diagonal(Z, 1)
    W = cholesky(Z, "L", nb=nb, precision=_hi(precision))
    B = trsm("R", "L", "C", W, X, nb=nb, precision=_hi(precision))   # X W^{-H}
    B = trsm("R", "L", "N", W, B, nb=nb, precision=_hi(precision))   # ... W^{-1}
    return X.with_local(keep * X.local + gain * B.local)


def _qdwh_step_qr(X: DistMatrix, sc, keep, gain, blocks,
                  precision) -> DistMatrix:
    """QR-variant step (numerically safe for huge c): with sc = sqrt(c),
    [sc X; I] = Q R, X' = keep X + gain Q1 Q2^H with keep = b / c,
    gain = (a - b / c) / sc.  The stack's lower block is the identity,
    which only this caller knows: the factorization and the thin Q run
    over the rows and columns that are not structurally zero
    (:func:`~.qr._stack_qr_thin_q`), 6 n^3 flops a square step for the
    general route's 34/3 n^3."""
    Q1, Q2 = _stack_qr_thin_q(X, sc, nb=blocks["qr"],
                              precision=_hi(precision))
    G = gemm(Q1, Q2, orient_b="C", nb=blocks["chol"],
             precision=_hi(precision))
    return X.with_local(keep * X.local + gain * G.local)


def _qdwh_coefficients(kind: str, a: float, b: float, c: float):
    """A step's three scalars, in double on the host: the weight of
    ``X^H X`` (its root in the QR form), and the weights of X and of the
    step's product in the update."""
    keep = b / c
    if kind == "qr":
        return math.sqrt(c), keep, (a - keep) / math.sqrt(c)
    return c, keep, a - keep


def _qdwh_run(X: DistMatrix, kind: str, first: int, params, blocks,
              precision) -> DistMatrix:
    """Consecutive steps ``first .. first + len(params) - 1`` of ONE
    variant: one step inline, more as ONE ``lax.fori_loop`` body over the
    table of their scalars (a step's program is tens of thousands of
    lines at size, and the compile cache takes no entry over 192 MiB:
    ``perf/program_size.py``).  The ops carry ``qdwh_<kind><first>`` or
    ``qdwh_<kind><first>_<last>``, opened inside the body."""
    step = {"qr": _qdwh_step_qr, "chol": _qdwh_step_chol}[kind]
    last = first + len(params) - 1
    name = f"qdwh_{kind}{first:02d}" + (f"_{last:02d}" if last > first else "")
    table = [_qdwh_coefficients(kind, *abc) for abc in params]
    _metrics.inc("qdwh_step", len(table), kind=kind)
    if len(table) == 1:
        with jax.named_scope(name):
            return step(X, *table[0], blocks, precision)
    scalars = jnp.asarray(table, _real_dtype(X.dtype))

    def body(k, X):          # a new function a loop: jax keys its trace by it
        with jax.named_scope(name):
            return step(X, scalars[k, 0], scalars[k, 1], scalars[k, 2],
                        blocks, precision)

    with _metrics.repeated(len(table)):
        return lax.fori_loop(0, len(table), body, X)


def _polar_blocks(nb, m: int, n: int, grid, dtype) -> dict:
    """``{stage: nb}`` of :func:`polar` and ``svd``'s polar route on an
    (m, n) operand, m >= n: an explicit ``nb`` goes to every stage; with
    ``nb=None`` each is picked from its shape, the grid and the dtype
    (:func:`~elemental_tpu.tune.policy.stage_blocksize`): ``qr`` for the
    QR-based steps' factorization and thin Q of the (m + n, n) stack (the
    panels are column loops), ``chol`` for the Cholesky-based steps'
    ``herk``, ``cholesky`` and ``trsm`` and for the outer ``gemm``s, ``eig``
    for ``svd``'s inner ``herm_eig``.  Whoever runs a stage ticks
    ``polar_block{stage,nb}`` for it."""
    if nb is not None:
        return {"qr": nb, "chol": nb, "eig": nb}
    return {"qr": stage_blocksize("qr", n, grid, dtype),
            "chol": stage_blocksize("block", n, grid, dtype),
            "eig": stage_blocksize("reduce", n, grid, dtype)}


@_scoped("el.polar")
def polar(A: DistMatrix, nb: int | None = None, precision=None,
          l_min: float | None = None, qr_c_switch: float = 100.0):
    """Polar decomposition ``A = U H`` with U a partial isometry (m >= n:
    U^H U = I) and H Hermitian PSD (Elemental ``El::Polar``, QDWH variant).

    ``l_min``: lower bound on sigma_min(A)/sigma_max(A) (defaults to ~eps of
    the dtype -- an underestimate only adds iterations).

    ONE traceable program: the scale ``alpha`` and the degenerate case (a
    zero or non-finite operand: ``U = I``, ``H = 0``) are values on the
    device, the (a, b, c) schedule is static, and every step of it runs.
    With ``nb=None`` each stage's block is :func:`_polar_blocks`'s.  Its ops
    carry ``el.polar/<segment>``: ``qdwh_qr<first>_<last>`` |
    ``qdwh_chol<first>_<last>`` around the consecutive steps of one variant
    (numbered from 01, ONE loop body over their scalars: :func:`_qdwh_run`;
    not a ``k<step>``, so the nested drivers' ops keep their own phase),
    ``polar_h`` around ``H = U^H A``; ``qdwh_step{kind=qr|chol}`` counts
    the steps the device runs.  A QR-based step factors ``[sqrt(c) X; I]``
    and forms its thin Q over the rows and columns that are not
    structurally zero (``el.qr`` and ``el.thin_q/k<panel>/apply`` under
    the step's segment) and ticks ``qdwh_stack_qr{route=structured}``
    (``dense`` where a grid's grain keeps some panel's rows whole)."""
    _check_mcmr(A)
    m, n = A.gshape
    if m < n:
        # A^H = W K  =>  A = (W^H)(W K W^H)
        W, K = polar(redistribute(transpose_dist(A, conj=True), MC, MR),
                     nb=nb, precision=_hi(precision), l_min=l_min)
        U = redistribute(transpose_dist(W, conj=True), MC, MR)
        H = gemm(gemm(W, K, nb=nb, precision=_hi(precision)), W, orient_b="C",
                 nb=nb, precision=_hi(precision))
        return U, _hermitianize(H)

    blocks = _polar_blocks(nb, m, n, A.grid, A.dtype)
    for stage in ("qr", "chol"):
        _metrics.inc("polar_block", stage=stage, nb=str(blocks[stage]))
    rdtype = _real_dtype(A.dtype)
    # sqrt(|A|_1 |A|_inf) >= |A|_2, as the product of the roots: the
    # product of the norms leaves the dtype's range where neither root does
    n1, ni = one_norm(A), infinity_norm(A)
    degenerate = ~(jnp.isfinite(n1) & jnp.isfinite(ni) & (n1 > 0))
    alpha = jnp.where(degenerate, 1,
                      jnp.sqrt(n1) * jnp.sqrt(ni)).astype(rdtype)
    X = A.with_local((A.local / alpha).astype(A.dtype))
    eps = _eps_of(A.dtype)
    l0 = l_min if l_min is not None else eps
    schedule = _qdwh_schedule(l0, tol=10 * eps)
    kinds = ["qr" if c > qr_c_switch else "chol" for _a, _b, c in schedule]
    first = 0
    for kind, run in itertools.groupby(kinds):
        count = len(list(run))
        X = _qdwh_run(X, kind, first + 1, schedule[first:first + count],
                      blocks, precision)
        first += count
    with jax.named_scope("polar_h"):
        H = _hermitianize(gemm(X, A, orient_a="C", nb=blocks["chol"],
                               precision=_hi(precision)))
    U = X.with_local(jnp.where(degenerate, _identity_like(A, m, n).local,
                               X.local))
    return U, H.with_local(jnp.where(degenerate, 0, H.local))


# ---------------------------------------------------------------------
# Matrix sign (Newton with norm scaling)
# ---------------------------------------------------------------------

def sign(A: DistMatrix, nb: int | None = None, precision=None,
         maxiter: int = 40, tol: float | None = None) -> DistMatrix:
    """Matrix sign function via scaled Newton iteration
    ``X <- (mu X + (mu X)^{-1}) / 2`` (``El::Sign``,
    ``src/lapack_like/funcs/Sign.cpp``; the Schur-SDC / Sylvester engine).

    Requires A to have no purely-imaginary eigenvalues (no eigenvalue on the
    unit... imaginary axis).  Host convergence loop over jitted device
    iterations (SURVEY.md §8.1 item 6)."""
    _check_mcmr(A)
    n = A.gshape[0]
    if A.gshape != (n, n):
        raise ValueError(f"sign needs square, got {A.gshape}")
    eps = _eps_of(A.dtype)
    tol = tol if tol is not None else n * 10 * eps
    X = A
    I = _identity_like(A, n)
    for it in range(maxiter):
        Xi = lu_solve(X, I, nb=nb, precision=_hi(precision))
        nx = float(frobenius_norm(X))
        ni = float(frobenius_norm(Xi))
        if not np.isfinite(nx) or not np.isfinite(ni):
            raise FloatingPointError("sign iteration diverged (singular A?)")
        mu = math.sqrt(ni / nx) if it < maxiter - 1 else 1.0
        Xnew = X.with_local(0.5 * (mu * X.local + (1.0 / mu) * Xi.local))
        delta = float(frobenius_norm(X.with_local(Xnew.local - X.local)))
        X = Xnew
        if delta <= tol * max(float(frobenius_norm(X)), 1e-30):
            break
    return X


# ---------------------------------------------------------------------
# Inverse family
# ---------------------------------------------------------------------

def inverse(A: DistMatrix, nb: int | None = None, precision=None) -> DistMatrix:
    """A^{-1} via LU with partial pivoting (``El::Inverse``,
    ``src/lapack_like/funcs/Inverse/General/``)."""
    _check_mcmr(A)
    n = A.gshape[0]
    if A.gshape != (n, n):
        raise ValueError(f"inverse needs square, got {A.gshape}")
    return lu_solve(A, _identity_like(A, n), nb=nb, precision=_hi(precision))


def triangular_inverse(uplo: str, A: DistMatrix, unit: bool = False,
                       nb: int | None = None, precision=None) -> DistMatrix:
    """inv(tri(A)) (``El::TriangularInverse``)."""
    _check_mcmr(A)
    n = A.gshape[0]
    return trsm("L", uplo, "N", A, _identity_like(A, n), unit=unit,
                nb=nb, precision=_hi(precision))


def hpd_inverse(A: DistMatrix, uplo: str = "L", nb: int | None = None,
                precision=None) -> DistMatrix:
    """Inverse of an HPD matrix via Cholesky (``El::HPDInverse``)."""
    _check_mcmr(A)
    n = A.gshape[0]
    return hpd_solve(A, _identity_like(A, n), uplo, nb=nb, precision=_hi(precision))


def pseudoinverse(A: DistMatrix, tol: float | None = None,
                  nb: int | None = None, precision=None) -> DistMatrix:
    """Moore-Penrose pseudoinverse via the SVD (``El::Pseudoinverse``):
    columns with s_i <= tol (default max(m,n) eps s_max) are dropped."""
    from ..blas.level1 import diagonal_scale
    from .spectral import svd
    m, n = A.gshape
    U, s, V = svd(A, vectors=True, nb=nb, precision=_hi(precision))
    smax = float(s[0]) if s.shape[0] else 0.0
    cut = tol if tol is not None else max(m, n) * _eps_of(A.dtype) * smax
    sinv = jnp.where(s > cut, 1.0 / jnp.where(s > cut, s, 1.0), 0.0)
    d = DistMatrix(sinv[:, None].astype(A.dtype), (s.shape[0], 1),
                   STAR, STAR, 0, 0, A.grid)
    Vs = diagonal_scale("R", d, V)
    return gemm(Vs, U, orient_b="C", nb=nb, precision=_hi(precision))


# ---------------------------------------------------------------------
# Square roots
# ---------------------------------------------------------------------

def square_root(A: DistMatrix, nb: int | None = None, precision=None,
                maxiter: int = 30, tol: float | None = None) -> DistMatrix:
    """Principal square root via the Denman-Beavers iteration
    (``El::SquareRoot`` uses the same Newton family):
    ``Y <- (Y + Z^{-1})/2, Z <- (Z + Y^{-1})/2``; Y -> A^{1/2}.

    Requires A to have no eigenvalues on the closed negative real axis."""
    _check_mcmr(A)
    n = A.gshape[0]
    if A.gshape != (n, n):
        raise ValueError(f"square_root needs square, got {A.gshape}")
    eps = _eps_of(A.dtype)
    tol = tol if tol is not None else n * 10 * eps
    I = _identity_like(A, n)
    Y, Z = A, I
    for _ in range(maxiter):
        Yi = lu_solve(Y, I, nb=nb, precision=_hi(precision))
        Zi = lu_solve(Z, I, nb=nb, precision=_hi(precision))
        Ynew = Y.with_local(0.5 * (Y.local + Zi.local))
        Z = Z.with_local(0.5 * (Z.local + Yi.local))
        delta = float(frobenius_norm(Y.with_local(Ynew.local - Y.local)))
        Y = Ynew
        if delta <= tol * max(float(frobenius_norm(Y)), 1e-30):
            break
    return Y


def hpd_square_root(A: DistMatrix, uplo: str = "L", nb: int | None = None,
                    precision=None) -> DistMatrix:
    """A^{1/2} of an HPD matrix via its eigendecomposition
    (``El::HPSDSquareRoot`` analog): Z diag(sqrt(w)) Z^H."""
    from ..blas.level1 import diagonal_scale
    from .spectral import herm_eig
    w, Z = herm_eig(A, uplo, vectors=True, nb=nb, precision=_hi(precision))
    sw = jnp.sqrt(jnp.clip(w, 0, None)).astype(A.dtype)
    d = DistMatrix(sw[:, None], (w.shape[0], 1), STAR, STAR, 0, 0, A.grid)
    Zs = diagonal_scale("R", d, Z)
    return gemm(Zs, Z, orient_b="C", nb=nb, precision=_hi(precision))


# ---------------------------------------------------------------------
# QDWH-eig: polar-based spectral divide and conquer
# ---------------------------------------------------------------------

def _replicated_eig(A: DistMatrix, vectors: bool):
    """Base case: gather the (small) block and solve redundantly."""
    n = A.gshape[0]
    Ag = redistribute(A, STAR, STAR).local
    w, Z = jnp.linalg.eigh(Ag)
    w = w.astype(_real_dtype(A.dtype))
    if not vectors:
        return w, None
    Zd = redistribute(
        DistMatrix(Z.astype(A.dtype), (n, n), STAR, STAR, 0, 0, A.grid),
        MC, MR)
    return w, Zd


def _dc_eig(A: DistMatrix, vectors: bool, nb, precision, base: int,
            seed: int, depth: int = 0):
    """Recursive QDWH-eig on a FULL (both triangles stored) Hermitian
    [MC,MR] matrix.  Returns (w ascending replicated, Z or None)."""
    n = A.gshape[0]
    g = A.grid
    if n <= max(base, 2) or depth > 60:
        return _replicated_eig(A, vectors)
    d = jnp.real(get_diagonal(A).local[:, 0])
    sigma = float(jnp.median(d))
    scale = max(float(frobenius_norm(A)), 1e-30)
    for attempt in range(3):
        As = shift_diagonal(A, -sigma)
        # U = sign(A - sigma I) via QDWH polar (Hermitian => polar == sign)
        U, _H = polar(As, nb=nb, precision=_hi(precision))
        # projector onto the eigenspace below sigma: P = (I - U)/2
        P = shift_diagonal(U.with_local(-0.5 * U.local), 0.5)
        k = int(round(float(jnp.real(dm_trace(P)))))
        if 0 < k < n:
            break
        # split failed: all eigenvalues on one side of sigma.  If the block
        # is (numerically) a multiple of the identity, deflate outright.
        rms = float(frobenius_norm(As)) / math.sqrt(n)
        if rms <= 10 * n * _eps_of(A.dtype) * scale:
            w = jnp.full((n,), sigma, _real_dtype(A.dtype))
            return (w, _identity_like(A, n) if vectors else None)
        sigma = sigma + rms if k == 0 else sigma - rms
    else:
        # could not find a splitting shift (pathological clustering):
        # correctness fallback
        return _replicated_eig(A, vectors)

    # orthonormal basis of range(P) via randomized range-finder + QR:
    # P is an exact projector up to rounding, so one multiply suffices and
    # the remaining Householder columns span the complement exactly.
    rng = np.random.default_rng(0xE1E0 + 31 * seed + depth)
    G = rng.normal(size=(n, k)).astype(np.float64)
    from ..core.distmatrix import from_global
    Gd = from_global(G.astype(np.dtype(_real_dtype(A.dtype))), MC, MR,
                     grid=g).astype(A.dtype)
    Y = gemm(P, Gd, nb=nb, precision=_hi(precision))
    Qp, tau = qr(Y, nb=nb, precision=_hi(precision))
    # C = Q^H A Q  (two packed-reflector applications + a transposition)
    T1 = apply_q(Qp, tau, A, orient="C", nb=nb, precision=_hi(precision))
    T2 = redistribute(transpose_dist(T1, conj=True), MC, MR)
    T3 = apply_q(Qp, tau, T2, orient="C", nb=nb, precision=_hi(precision))
    C = redistribute(transpose_dist(T3, conj=True), MC, MR)
    A1 = _hermitianize(interior_view(C, (0, k), (0, k)))
    A2 = _hermitianize(interior_view(C, (k, n), (k, n)))
    w1, Z1 = _dc_eig(A1, vectors, nb, precision, base, 2 * seed + 1, depth + 1)
    w2, Z2 = _dc_eig(A2, vectors, nb, precision, base, 2 * seed + 2, depth + 1)
    w = jnp.concatenate([w1, w2])
    if not vectors:
        return w, None
    BD = _blank(n, n, A)
    BD = interior_update(BD, Z1, (0, 0))
    BD = interior_update(BD, Z2, (k, k))
    Z = apply_q(Qp, tau, BD, orient="N", nb=nb, precision=_hi(precision))
    return w, Z


def _qdwh_eig(A: DistMatrix, uplo: str = "L", vectors: bool = True,
              subset=None, nb: int | None = None, precision=None,
              base: int | None = None):
    """Spectral divide-and-conquer eigensolver (QDWH-eig, the PMRRR
    replacement -- SURVEY.md §8.1 item 4).  No O(n^2)-replicated construct:
    splits ride :mod:`..redist.interior`, the base case gathers only
    ``base x base`` blocks."""
    from .spectral import _subset_slice
    _check_mcmr(A)
    n = A.gshape[0]
    if A.gshape != (n, n):
        raise ValueError(f"_qdwh_eig needs square, got {A.gshape}")
    full = make_symmetric(A, uplo, conj=True)
    base = base if base is not None else 128
    w, Z = _dc_eig(full, vectors, nb, precision, base, seed=1)
    # guard the seams: blocks are spectrum-ordered by construction, but
    # boundary rounding can micro-misorder; sort if needed.
    order = jnp.argsort(w)
    w = w[order]
    s, e = _subset_slice(w, subset)
    if not vectors:
        return w[s:e]
    from .lu import permute_cols
    Z = permute_cols(Z, order)
    if (s, e) != (0, n):
        Z = interior_view(Z, (0, n), (s, e))
    return w[s:e], Z
