"""Interior (arbitrary-offset) submatrix extraction and embedding.

The reference reads/writes arbitrary interior submatrices through FLAME
views plus alignment-shifted redistributions (Elemental
``include/El/core/View.hpp`` views carry nonzero alignments;
``copy::ColAlign``-style shifts re-land them).  Our storage views
(:mod:`..core.view`) are pure-local but require stride-grain offsets; this
module supplies the general case as a standalone op:

  * :func:`interior_view`   -- ``B = A[s:e, s2:e2]`` as a NEW zero-aligned
    DistMatrix with the same distribution pair.
  * :func:`interior_update` -- functionally write ``B`` into ``A`` at an
    arbitrary ``(i0, j0)`` offset.
  * :func:`grain_view` / :func:`grain_update` -- the same two at a
    stride-grain offset that may be TRACED (a loop's counter): one local
    ``dynamic_slice`` / ``dynamic_update_slice`` a device, no exchange.

TPU-native cost model: a global range whose start ``s`` is NOT a stride
multiple shifts every row's owner by the fixed rotation ``s mod S`` -- so
the whole move is ONE ``lax.ppermute`` rotation per distributed dim plus a
per-device static local slice (no all-to-all, no replication).  This is the
communication-optimal analog of the reference's aligned-copy kernels and
the tool that lets divide-and-conquer algorithms (QDWH-eig, Schur-SDC)
split at data-dependent spectral boundaries.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..core import indexing as ix
from ..core.compat import shard_map
from ..core.dist import Dist, MC, MR, VC, VR, stride as dist_stride, rank_of
from ..core.distmatrix import DistMatrix


def _pad_dim(x, dim: int, target: int):
    cur = x.shape[dim]
    if cur >= target:
        return x
    pads = [(0, 0)] * x.ndim
    pads[dim] = (0, target - cur)
    return jnp.pad(x, pads)


def _rot_perm(d: Dist, delta: int, r: int, c: int):
    """(axes, perm) rotating rank space by ``delta``: rank q receives from
    rank (q + delta) % S.  ppermute's multi-axis linear id follows MESH order
    (mc major; verified empirically -- the tuple order given is ignored), so
    VC's column-major rank is translated to device ids explicitly."""
    if d is MC:
        S = r
        return "mc", [((q + delta) % S, q) for q in range(S)]
    if d is MR:
        S = c
        return "mr", [((q + delta) % S, q) for q in range(S)]
    p = r * c
    if d is VC:
        lin = [(v % r) * c + v // r for v in range(p)]   # device id of VC rank v
    elif d is VR:
        lin = list(range(p))                             # VR rank == device id
    else:
        raise ValueError(f"no permute axes for {d}")
    return ("mc", "mr"), [(lin[(q + delta) % p], lin[q]) for q in range(p)]


def _extract_dim(x, dim: int, d: Dist, s: int, e: int, r: int, c: int):
    """One dim of the extract: rows [s, e) -> new zero-aligned dim."""
    S = dist_stride(d, r, c)
    if S == 1:
        return lax.slice_in_dim(x, s, e, axis=dim)
    l_new = ix.max_local_length(e - s, S)
    if s % S:
        axes, perm = _rot_perm(d, s % S, r, c)
        x = lax.ppermute(x, axes, perm)
    x = _pad_dim(x, dim, s // S + 1 + l_new)
    q = rank_of(d, r, c)
    o = (q + s) // S
    y = lax.dynamic_slice_in_dim(x, o, l_new, axis=dim)
    gi = jnp.arange(l_new) * S + q            # new global index of each slot
    shape = [1] * y.ndim
    shape[dim] = l_new
    return jnp.where((gi < (e - s)).reshape(shape), y, 0)


def _embed_dim(big, small, dim: int, d: Dist, s: int, h: int, r: int, c: int):
    """One dim of the embed: write ``small`` (extent h) at offset ``s``."""
    S = dist_stride(d, r, c)
    if S == 1:
        return lax.dynamic_update_slice_in_dim(big, small, s, axis=dim)
    l_small = small.shape[dim]
    if s % S:
        axes, perm = _rot_perm(d, -(s % S) % S, r, c)
        small = lax.ppermute(small, axes, perm)
    q = rank_of(d, r, c)
    qB = (q - s) % S                          # source rank of the held block
    o = (qB + s) // S
    gj = jnp.arange(l_small) * S + qB         # source global index per slot
    shape = [1] * small.ndim
    shape[dim] = l_small
    valid = (gj < h).reshape(shape)
    orig = big.shape[dim]
    big = _pad_dim(big, dim, s // S + 1 + l_small)
    seg = lax.dynamic_slice_in_dim(big, o, l_small, axis=dim)
    seg = jnp.where(valid, small, seg)
    out = lax.dynamic_update_slice_in_dim(big, seg, o, axis=dim)
    if out.shape[dim] != orig:
        out = lax.slice_in_dim(out, 0, orig, axis=dim)
    return out


def _check_zero_aligned(*Ms: DistMatrix):
    for A in Ms:
        if (A.calign, A.ralign) != (0, 0):
            raise ValueError(f"interior ops require zero alignment, got {A}")


@partial(jax.jit, static_argnums=(1, 2))
def interior_view(A: DistMatrix, rows=None, cols=None) -> DistMatrix:
    """``A[rows[0]:rows[1], cols[0]:cols[1]]`` as a new zero-aligned
    DistMatrix (same distribution pair), for ARBITRARY offsets."""
    _check_zero_aligned(A)
    m, n = A.gshape
    rows = (0, m) if rows is None else rows
    cols = (0, n) if cols is None else cols
    (rs, re), (cs, ce) = rows, cols
    if not (0 <= rs <= re <= m and 0 <= cs <= ce <= n):
        raise ValueError(f"range ({rows},{cols}) out of bounds for {A.gshape}")
    g = A.grid
    r, c = g.height, g.width
    out_meta = DistMatrix(None, (re - rs, ce - cs), A.cdist, A.rdist, 0, 0, g)

    def f(a):
        x = _extract_dim(a.local, 0, a.cdist, rs, re, r, c)
        x = _extract_dim(x, 1, a.rdist, cs, ce, r, c)
        return out_meta.with_local(x)

    return shard_map(f, mesh=g.mesh, in_specs=(A.spec,),
                         out_specs=out_meta.spec, check_vma=False)(A)


@partial(jax.jit, static_argnums=(2,))
def interior_update(A: DistMatrix, B: DistMatrix, at=(0, 0)) -> DistMatrix:
    """Functionally write ``B`` into ``A`` starting at global ``at=(i0,j0)``
    (arbitrary offsets; B must share A's distribution pair and grid)."""
    _check_zero_aligned(A, B)
    if B.dist != A.dist or B.grid != A.grid:
        raise ValueError(f"interior_update needs matching layout: {A} vs {B}")
    i0, j0 = at
    m, n = A.gshape
    h, w = B.gshape
    if i0 + h > m or j0 + w > n:
        raise ValueError(f"block {B.gshape} at {at} exceeds {A.gshape}")
    g = A.grid
    r, c = g.height, g.width

    def f(a, b):
        loc = a.local
        # 1. pull out the column strip [j0, j0+w) of A (full rows, B's cols)
        strip = _extract_dim(loc, 1, a.rdist, j0, j0 + w, r, c)
        # 2. embed B's rows into the strip at row offset i0
        strip = _embed_dim(strip, b.local, 0, a.cdist, i0, h, r, c)
        # 3. write the strip back into A's columns at offset j0
        loc = _embed_dim(loc, strip, 1, a.rdist, j0, w, r, c)
        return a.with_local(loc)

    return shard_map(f, mesh=g.mesh, in_specs=(A.spec, B.spec),
                         out_specs=A.spec, check_vma=False)(A, B)


def _grain_block(A: DistMatrix, at, shape):
    """Local offsets and extents of the stride-grain block ``shape`` at
    ``at`` of ``A``; a static offset or extent off the grain raises, a
    traced offset is the caller's promise."""
    _check_zero_aligned(A)
    strides = (A.col_stride, A.row_stride)
    for x, S in zip(tuple(shape) + tuple(at), strides + strides):
        if isinstance(x, int) and x % S:
            raise ValueError(f"block {shape} at {at} is off the grain "
                             f"{strides} of {A}")
    offs = tuple(jnp.asarray(o, jnp.int32) // S for o, S in zip(at, strides))
    return offs, tuple(x // S for x, S in zip(shape, strides))


def grain_view(A: DistMatrix, at, shape) -> DistMatrix:
    """``A[i0:i0+h, j0:j0+w]`` for ``at=(i0, j0)``, ``shape=(h, w)`` all
    multiples of their dim's stride, as a new zero-aligned DistMatrix.
    ``at`` may be TRACED (``shape`` is static), which is what
    :func:`interior_view` cannot take: every device owns the same local
    window of such a block, so the view is one ``dynamic_slice`` of the
    local block and a loop can walk a matrix by blocks with ONE compiled
    body."""
    offs, ext = _grain_block(A, at, shape)
    out_meta = DistMatrix(None, tuple(shape), A.cdist, A.rdist, 0, 0, A.grid)

    def f(a, i, j):
        return out_meta.with_local(lax.dynamic_slice(a.local, (i, j), ext))

    return shard_map(f, mesh=A.grid.mesh, in_specs=(A.spec, P(), P()),
                     out_specs=out_meta.spec, check_vma=False)(A, *offs)


def grain_update(A: DistMatrix, B: DistMatrix, at) -> DistMatrix:
    """Functionally write ``B`` into ``A`` at ``at=(i0, j0)``, offsets and
    B's extents multiples of their dim's stride; ``at`` may be TRACED
    (:func:`grain_view`'s mirror: one local ``dynamic_update_slice``)."""
    if B.dist != A.dist or B.grid != A.grid:
        raise ValueError(f"grain_update needs matching layout: {A} vs {B}")
    _check_zero_aligned(B)
    offs, _ = _grain_block(A, at, B.gshape)

    def f(a, b, i, j):
        return a.with_local(lax.dynamic_update_slice(a.local, b.local, (i, j)))

    return shard_map(f, mesh=A.grid.mesh, in_specs=(A.spec, B.spec, P(), P()),
                     out_specs=A.spec, check_vma=False)(A, B, *offs)


# ---------------------------------------------------------------------
# stacking helpers (QDWH's [sqrt(c) X; I] and friends)
# ---------------------------------------------------------------------

def _blank(m: int, n: int, like: DistMatrix) -> DistMatrix:
    meta = DistMatrix(None, (m, n), like.cdist, like.rdist, 0, 0, like.grid)
    stor = jnp.zeros((meta.col_stride * meta.local_rows,
                      meta.row_stride * meta.local_cols), like.dtype)
    return meta.with_local(stor)


def vstack(A: DistMatrix, B: DistMatrix) -> DistMatrix:
    """[A; B] (concatenate rows) with A's distribution pair."""
    if A.gshape[1] != B.gshape[1]:
        raise ValueError(f"vstack width mismatch {A.gshape} vs {B.gshape}")
    out = _blank(A.gshape[0] + B.gshape[0], A.gshape[1], A)
    out = interior_update(out, A, (0, 0))
    return interior_update(out, B, (A.gshape[0], 0))


def hstack(A: DistMatrix, B: DistMatrix) -> DistMatrix:
    """[A, B] (concatenate columns) with A's distribution pair."""
    if A.gshape[0] != B.gshape[0]:
        raise ValueError(f"hstack height mismatch {A.gshape} vs {B.gshape}")
    out = _blank(A.gshape[0], A.gshape[1] + B.gshape[1], A)
    out = interior_update(out, A, (0, 0))
    return interior_update(out, B, (0, A.gshape[1]))
