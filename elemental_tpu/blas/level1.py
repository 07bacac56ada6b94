"""Level-1 zoo.

Reference: Elemental ``src/blas_like/level1/*.cpp`` (~70 files: Axpy, Scale,
Dot, Nrm2, Zero, Fill, EntrywiseMap, Hadamard, MakeTrapezoidal,
MakeSymmetric/Hermitian, DiagonalScale, GetDiagonal/SetDiagonal, ...).

TPU-native design point: because the stacked-storage array contains every
global entry EXACTLY ONCE (replication lives at the device level, not in the
storage array) and padding is zero, elementwise ops between same-distribution
operands and all entrywise reductions run directly on storage arrays OUTSIDE
shard_map -- XLA/GSPMD handles the sharded arithmetic.  Only index-dependent
ops (trapezoidal masks, diagonals) need the cyclic index maps.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.dist import Dist
from ..core.distmatrix import DistMatrix
from ..redist.engine import redistribute, transpose_dist


def _check_same_layout(A: DistMatrix, B: DistMatrix):
    if A.dist != B.dist or (A.calign, A.ralign) != (B.calign, B.ralign) \
            or A.gshape != B.gshape or A.grid != B.grid:
        raise ValueError(f"layout mismatch: {A} vs {B}")


# ---- elementwise ----------------------------------------------------

def axpy(alpha, X: DistMatrix, Y: DistMatrix) -> DistMatrix:
    _check_same_layout(X, Y)
    return Y.with_local(alpha * X.local + Y.local)


def scale(alpha, A: DistMatrix) -> DistMatrix:
    return A.with_local(alpha * A.local)


def zero(A: DistMatrix) -> DistMatrix:
    return A.with_local(jnp.zeros_like(A.local))


def fill(A: DistMatrix, value) -> DistMatrix:
    """Fill with a constant (padding kept zero via the global-index mask)."""
    mask = _valid_mask(A)
    return A.with_local(jnp.where(mask, jnp.asarray(value, A.dtype), 0))


def entrywise_map(A: DistMatrix, fn) -> DistMatrix:
    """EntrywiseMap; fn must map 0 -> 0 or the padding is re-zeroed."""
    out = fn(A.local)
    return A.with_local(jnp.where(_valid_mask(A), out, 0))


def hadamard(A: DistMatrix, B: DistMatrix) -> DistMatrix:
    _check_same_layout(A, B)
    return A.with_local(A.local * B.local)


def conjugate(A: DistMatrix) -> DistMatrix:
    return A.with_local(jnp.conj(A.local))


# ---- index-dependent maps -------------------------------------------

def _global_indices(A: DistMatrix):
    """(I, J) global index arrays matching the storage array layout."""
    m, n = A.gshape
    Sc, Sr = A.col_stride, A.row_stride
    lr, lc = A.local_rows, A.local_cols
    q = jnp.arange(Sc)[:, None]
    il = jnp.arange(lr)[None, :]
    I = (il * Sc + (q - A.calign) % Sc).reshape(-1)      # storage row -> global row
    q2 = jnp.arange(Sr)[:, None]
    jl = jnp.arange(lc)[None, :]
    J = (jl * Sr + (q2 - A.ralign) % Sr).reshape(-1)
    return I, J


def _valid_mask(A: DistMatrix):
    I, J = _global_indices(A)
    m, n = A.gshape
    return (I[:, None] < m) & (J[None, :] < n)


def index_dependent_map(A: DistMatrix, fn) -> DistMatrix:
    """IndexDependentMap: B[i,j] = fn(i, j, A[i,j]) (fn broadcast over index
    arrays); padding re-zeroed."""
    I, J = _global_indices(A)
    out = fn(I[:, None], J[None, :], A.local)
    return A.with_local(jnp.where(_valid_mask(A), out, 0))


def index_dependent_fill(A: DistMatrix, fn) -> DistMatrix:
    """IndexDependentFill: B[i,j] = fn(i, j).

    The result does not read A's entries, so under ``jit`` no dataflow
    carries A's placement over to it: a fill traced with no sharded input
    lands whole on one device.  It is therefore pinned to A's storage
    sharding explicitly."""
    out = index_dependent_map(A, lambda i, j, a: fn(i, j) + jnp.zeros_like(a))
    if A.cdist is Dist.CIRC:             # [CIRC,CIRC] lives on one device
        return out
    return out.with_local(jax.lax.with_sharding_constraint(
        out.local, A.grid.sharding(A.spec)))


def make_trapezoidal(A: DistMatrix, uplo: str, offset: int = 0) -> DistMatrix:
    """Zero outside the lower/upper trapezoid (MakeTrapezoidal)."""
    I, J = _global_indices(A)
    if uplo.upper().startswith("L"):
        keep = J[None, :] <= I[:, None] + offset
    else:
        keep = J[None, :] >= I[:, None] + offset
    return A.with_local(jnp.where(keep, A.local, 0))


def shift_diagonal(A: DistMatrix, alpha, offset: int = 0) -> DistMatrix:
    """A += alpha*I on the given diagonal (ShiftDiagonal / UpdateDiagonal)."""
    I, J = _global_indices(A)
    m, n = A.gshape
    on = (J[None, :] == I[:, None] + offset) & (I[:, None] < m) & (J[None, :] < n)
    return A.with_local(A.local + jnp.where(on, jnp.asarray(alpha, A.dtype), 0))


def make_symmetric(A: DistMatrix, uplo: str = "L", conj: bool = False) -> DistMatrix:
    """Reflect the given triangle onto the other (MakeSymmetric/Hermitian).

    Implemented as trapezoid(A) + trapezoid(A)^T - diag, using the free
    transpose-dist + a redistribution back.
    """
    tri = make_trapezoidal(A, uplo, 0)
    triT = redistribute(transpose_dist(tri, conj=conj), *A.dist,
                        calign=A.calign, ralign=A.ralign)
    I, J = _global_indices(A)
    on_diag = J[None, :] == I[:, None]
    dvals = jnp.where(on_diag, tri.local, 0)
    if conj:
        dvals = jnp.real(dvals).astype(A.dtype)
    out = tri.local + triT.local - dvals
    return A.with_local(out)


def get_diagonal(A: DistMatrix, offset: int = 0, dist: str = "star"):
    """Diagonal of A as a (k, 1) DistMatrix.

    ``dist='star'`` (default): replicated [STAR,STAR] -- the convenient
    form every elementwise consumer here takes.  ``dist='md'``: TRUE
    [MD,STAR] output (the reference's return type): diagonal entry k of
    an [MC,MR] matrix lives on device (k%r, k%c), which IS its MD owner,
    so the extraction is device-co-located and the per-device allocation
    is O(k/lcm) -- no replicated k-vector exists."""
    if dist == "md":
        return _get_diagonal_md(A, offset)
    if dist != "star":
        raise ValueError(f"get_diagonal dist must be 'star' or 'md', "
                         f"got {dist!r}")
    m, n = A.gshape
    k = min(m, n - offset) if offset >= 0 else min(m + offset, n)
    I, J = _global_indices(A)
    on = J[None, :] == I[:, None] + offset
    # scatter local diag entries into a dense k-vector, then sum-replicate
    didx = jnp.where(on, I[:, None] - (0 if offset >= 0 else -offset), 0)
    contrib = jnp.zeros((max(k, 1),), A.dtype).at[
        jnp.where(on, didx, k if k > 0 else 0).reshape(-1)
    ].add(jnp.where(on, A.local, 0).reshape(-1), mode="drop")
    # storage arrays hold each entry once; sum over devices happens via GSPMD
    vec = contrib.reshape(k, 1) if k > 0 else jnp.zeros((0, 1), A.dtype)
    from ..core.dist import STAR as _S
    out = DistMatrix(vec, (k, 1), _S, _S, 0, 0, A.grid)
    return out


def _get_diagonal_md(A: DistMatrix, offset: int):
    """[MD,STAR] diagonal extraction (offset 0; co-located, O(k/lcm))."""
    from ..core.dist import MC as _MC, MR as _MR, MD as _MD, STAR as _S
    from ..core.dist import md_slot_of_global, stride as _stride
    from ..core import indexing as _ix
    if offset != 0:
        raise NotImplementedError("MD output supports the main diagonal")
    if (A.cdist, A.rdist) != (_MC, _MR) or A.calign or A.ralign:
        raise ValueError("MD extraction needs a zero-aligned [MC,MR] source")
    m, n = A.gshape
    k = min(m, n)
    r, c = A.grid.height, A.grid.width
    L = _stride(_MD, r, c)
    l = _ix.max_local_length(k, L)
    lr, lc = A.local_rows, A.local_cols
    # storage coordinates of global (kk, kk) and the MD slot it feeds;
    # both live on device (kk%r, kk%c), so XLA lowers this to local moves
    kk = jnp.arange(k)
    ri = (kk % r) * lr + kk // r
    cj = (kk % c) * lc + kk // c
    vals = A.local[ri, cj]
    slots = jnp.asarray(md_slot_of_global(r, c, k))
    stor = jnp.zeros((r * c * l, 1), A.dtype).at[slots, 0].set(vals)
    out = DistMatrix(stor, (k, 1), _MD, _S, 0, 0, A.grid)
    import jax as _jax
    return out.with_local(_jax.device_put(stor, A.grid.sharding(out.spec)))


def _diag_vals(A: DistMatrix, d: DistMatrix, offset: int):
    """(on-diagonal mask, broadcast diagonal values) shared by the
    set/update diagonal ops."""
    m, n = A.gshape
    I, J = _global_indices(A)
    on = (J[None, :] == I[:, None] + offset) \
        & (I[:, None] < m) & (J[None, :] < n)
    di = I[:, None] - (0 if offset >= 0 else -offset)
    dv = d.local.reshape(-1)
    vals = dv[jnp.clip(di, 0, max(dv.shape[0] - 1, 0))]
    return on, vals


def set_diagonal(A: DistMatrix, d: DistMatrix, offset: int = 0) -> DistMatrix:
    """Write a replicated (k,1) diagonal into A."""
    on, vals = _diag_vals(A, d, offset)
    return A.with_local(jnp.where(on, vals, A.local))


def update_diagonal(A: DistMatrix, d: DistMatrix, offset: int = 0) -> DistMatrix:
    """A += diag(d) on the given diagonal; d replicated (k,1)
    (``El::UpdateDiagonal`` with a vector)."""
    on, vals = _diag_vals(A, d, offset)
    return A.with_local(jnp.where(on, A.local + vals, A.local))


def diagonal_scale(side: str, d: DistMatrix, A: DistMatrix) -> DistMatrix:
    """A := diag(d) A (side=L) or A diag(d) (side=R); d replicated (k,1)."""
    I, J = _global_indices(A)
    dv = d.local.reshape(-1)
    if side.upper().startswith("L"):
        vals = dv[jnp.clip(I, 0, dv.shape[0] - 1)]
        return A.with_local(A.local * vals[:, None])
    vals = dv[jnp.clip(J, 0, dv.shape[0] - 1)]
    return A.with_local(A.local * vals[None, :])


def diagonal_solve(side: str, d: DistMatrix, A: DistMatrix) -> DistMatrix:
    dv = d.local.reshape(-1)
    dinv = jnp.where(dv != 0, 1 / jnp.where(dv == 0, 1, dv), 0)
    return diagonal_scale(side, d.with_local(dinv.reshape(-1, 1)), A)


# ---- reductions (storage-based: each entry once, padding zero) -------

def frobenius_norm(A: DistMatrix):
    return jnp.linalg.norm(A.local)


def max_norm(A: DistMatrix):
    return jnp.max(jnp.abs(A.local)) if A.local.size else jnp.asarray(0.0)


def one_norm(A: DistMatrix):
    """max column sum -- column permutation of storage is irrelevant."""
    return jnp.max(jnp.sum(jnp.abs(A.local), axis=0))


def infinity_norm(A: DistMatrix):
    return jnp.max(jnp.sum(jnp.abs(A.local), axis=1))


def entrywise_norm(A: DistMatrix, p):
    return jnp.sum(jnp.abs(A.local) ** p) ** (1.0 / p)


def zero_norm(A: DistMatrix, tol=0.0):
    return jnp.sum(jnp.abs(A.local) > tol)


def dot(A: DistMatrix, B: DistMatrix):
    """Hilbert-Schmidt inner product <A,B> = sum conj(A) * B."""
    _check_same_layout(A, B)
    return jnp.sum(jnp.conj(A.local) * B.local)


def nrm2(A: DistMatrix):
    return frobenius_norm(A)


def trace(A: DistMatrix):
    d = get_diagonal(A)
    return jnp.sum(d.local)


# ---- orientation / parts (Transpose.cpp, RealPart.cpp, Conjugate.cpp) ----

def transpose(A: DistMatrix, conj: bool = False) -> DistMatrix:
    """B = A^T (``El::Transpose``): free dist-transpose + engine hops back to
    A's distribution pair."""
    return redistribute(transpose_dist(A, conj=conj), *A.dist,
                        calign=A.calign, ralign=A.ralign)


def adjoint(A: DistMatrix) -> DistMatrix:
    """B = A^H (``El::Adjoint``)."""
    return transpose(A, conj=True)


def real_part(A: DistMatrix) -> DistMatrix:
    """``El::RealPart`` (result is the real base dtype)."""
    return A.with_local(jnp.real(A.local))


def imag_part(A: DistMatrix) -> DistMatrix:
    """``El::ImagPart``."""
    return A.with_local(jnp.imag(A.local))


def round_entries(A: DistMatrix) -> DistMatrix:
    """``El::Round``: nearest integer, entrywise (complex: each part)."""
    if jnp.iscomplexobj(A.local):
        return A.with_local(jnp.round(jnp.real(A.local))
                            + 1j * jnp.round(jnp.imag(A.local)))
    return A.with_local(jnp.round(A.local))


def swap(A: DistMatrix, B: DistMatrix):
    """``El::Swap``: functionally, just the exchanged pair."""
    _check_same_layout(A, B)
    return B, A


def dotu(A: DistMatrix, B: DistMatrix):
    """Non-conjugated inner product (``El::Dotu``)."""
    _check_same_layout(A, B)
    return jnp.sum(A.local * B.local)


# ---- extremal entries with location (MaxAbsLoc / MaxLoc family) ------

def _loc_reduce(A: DistMatrix, vals, reducer):
    """Shared (value, (i,j)) reduction over the storage array: pack the
    global index into the comparison payload -- the ``mpi::MAXLOC`` analog
    (value,index) pairing, done as one argmax over each-entry-once storage."""
    I, J = _global_indices(A)
    m, n = A.gshape
    valid = (I[:, None] < m) & (J[None, :] < n)
    flat = jnp.where(valid, vals, reducer.pad).reshape(-1)
    idx = reducer.arg(flat)
    li, lj = idx // vals.shape[1], idx % vals.shape[1]
    return flat[idx], (I[li], J[lj])


class _MaxRed:
    pad = -jnp.inf
    arg = staticmethod(jnp.argmax)


class _MinRed:
    pad = jnp.inf
    arg = staticmethod(jnp.argmin)


def max_abs_loc(A: DistMatrix):
    """(|a_ij|max, (i,j)) -- ``El::MaxAbsLoc``; the LU pivot-search kernel."""
    return _loc_reduce(A, jnp.abs(A.local), _MaxRed)


def min_abs_loc(A: DistMatrix):
    """``El::MinAbsLoc``."""
    return _loc_reduce(A, jnp.abs(A.local), _MinRed)


def max_loc(A: DistMatrix):
    """``El::MaxLoc`` (real dtypes)."""
    return _loc_reduce(A, jnp.real(A.local), _MaxRed)


def min_loc(A: DistMatrix):
    """``El::MinLoc`` (real dtypes)."""
    return _loc_reduce(A, jnp.real(A.local), _MinRed)


# ---- trapezoid updates (ScaleTrapezoid.cpp, AxpyTrapezoid.cpp) -------

def _trapezoid_mask(A: DistMatrix, uplo: str, offset: int):
    I, J = _global_indices(A)
    if uplo.upper().startswith("L"):
        return J[None, :] <= I[:, None] + offset
    return J[None, :] >= I[:, None] + offset


def scale_trapezoid(alpha, A: DistMatrix, uplo: str, offset: int = 0
                    ) -> DistMatrix:
    """Scale the lower/upper trapezoid by alpha, rest untouched
    (``El::ScaleTrapezoid``)."""
    keep = _trapezoid_mask(A, uplo, offset)
    return A.with_local(jnp.where(keep, alpha * A.local, A.local))


def axpy_trapezoid(alpha, X: DistMatrix, Y: DistMatrix, uplo: str,
                   offset: int = 0) -> DistMatrix:
    """Y += alpha * trapezoid(X) (``El::AxpyTrapezoid``)."""
    _check_same_layout(X, Y)
    keep = _trapezoid_mask(X, uplo, offset)
    return Y.with_local(Y.local + jnp.where(keep, alpha * X.local, 0))


def safe_scale(numerator, denominator, A: DistMatrix):
    """A := (numerator/denominator) A staged to avoid overflow/underflow
    (``El::SafeScale``; the LAPACK ``dlascl`` multiplier-staging loop)."""
    import numpy as _np
    base = A.local.real.dtype if jnp.iscomplexobj(A.local) else A.local.dtype
    fin = _np.finfo(base)
    small, big = float(fin.tiny), 1.0 / float(fin.tiny)
    cfrom, cto = float(denominator), float(numerator)
    if cfrom == 0.0:
        raise ValueError("safe_scale: denominator must be nonzero")
    out = A
    while True:
        cfrom1 = cfrom * small
        cto1 = cto / big
        if abs(cfrom1) > abs(cto) and cto != 0.0:
            mul, cfrom = small, cfrom1
        elif abs(cto1) > abs(cfrom):
            mul, cto = big, cto1
        else:
            return out.with_local(out.local * (cto / cfrom))
        out = out.with_local(out.local * mul)


# ---- submatrix access (GetSubmatrix.cpp / SetSubmatrix.cpp) ----------

def get_submatrix(A: DistMatrix, i0: int, j0: int, m: int, n: int
                  ) -> DistMatrix:
    """Copy out A[i0:i0+m, j0:j0+n] as a zero-aligned matrix of the same
    distribution (``El::GetSubmatrix`` with contiguous ranges)."""
    from ..redist.interior import interior_view
    return interior_view(A, (i0, i0 + m), (j0, j0 + n))


def set_submatrix(A: DistMatrix, i0: int, j0: int, B: DistMatrix
                  ) -> DistMatrix:
    """Write B into A[i0:.., j0:..] (``El::SetSubmatrix``)."""
    from ..redist.interior import interior_update
    return interior_update(A, B, at=(i0, j0))
