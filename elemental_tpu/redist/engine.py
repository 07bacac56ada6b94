"""The redistribution engine.

TPU-native rebuild of the reference's ``El::copy`` namespace
(Elemental ``src/blas_like/level1/Copy/*.hpp`` -- ``AllGather``,
``ColAllGather``, ``PartialColAllGather``, ``Filter``, ``PartialColFilter``,
``Gather``, ``Scatter``, ...): ``B = A`` between any two of the legal
distribution pairs, implemented as named-axis collectives + pure-local
index shuffles inside ``shard_map``.

Structure:
  * ``_gather_dim``  -- dist dim -> replicated dim  (lax.all_gather + interleave)
  * ``_filter_dim``  -- replicated dim -> dist dim  (pure local selection)
  * partial gathers/filters for the V* <-> M* ladder
  * ``to_dist``      -- the dispatch table (fast paths, generic fallback
                        through [STAR,STAR] for the cold pairs)
  * ``contract``     -- the reference's ``Contract``/``AxpyContract``
                        (SumScatter of partial products; lowers to
                        ``lax.psum_scatter``)

Everything here assumes it is called INSIDE ``shard_map`` over the grid's
mesh; the public jit-able entry point is :func:`redistribute`.

Alignment support: the generic path handles arbitrary alignments; fast paths
currently require zero alignments (the blocked algorithms only use zero) and
fall back otherwise.

Every primitive here is a local step, one explicit collective, a local step,
and names them so under its caller's ``el.redist.<name>`` scope
(``obs.redist_part``, grammar in :mod:`elemental_tpu.obs`): ``pack`` (what
feeds the collective: ``_pad_dim``, the reshape into per-peer blocks, the
cast or ``q8_pack`` to the wire dtype, ``_direct_exec``'s table gather),
``wire`` (the ``lax`` collective call and nothing else), ``unpack`` (what
follows: ``_interleave``, ``_deinterleave``, the ``_filter_*`` family, the
slices, ``_zero_padding`` and the masks, ``q8_unpack``, ``_direct_exec``'s
scatter; all of an exchange that has no collective).  ``move_rows`` and
``permute_rows_storage`` issue no collective of their own and name no part:
``benchmark/redist_parts.py`` books them as ``planned``.
"""
from __future__ import annotations

import contextlib
import dataclasses
from collections import Counter
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
from jax import lax

from ..core import indexing as ix
from ..core.compat import shard_map
from ..core.dist import (
    Dist, MC, MR, VC, VR, STAR, MD, CIRC,
    stride as dist_stride, gather_axes, rank_of, md_slot_of_global,
)
from ..core.distmatrix import DistMatrix, _check_pair
from ..obs import metrics as _metrics
from ..obs.tracer import redist_part as _part, scoped as _scoped
from .plan import compile_plan
from .quantize import (QUANT_TILE, check_comm_precision, q8_pack, q8_unpack,
                       quantizable)

#: legal values of :func:`redistribute`'s ``path`` argument.  ``None`` and
#: ``'chain'`` are the factored multi-hop route (bit-identical to the
#: pre-ISSUE-12 engine); ``'direct'`` executes the one-shot compiled plan
#: (:mod:`.plan`) where one exists, falling back to the chain otherwise;
#: ``'auto'`` arbitrates per call with the ring-model cost below.
REDIST_PATHS = (None, "chain", "direct", "auto")


#: Trace-time instrumentation: public-entry call counts, keyed by
#: ``(src_dist_pair, dst_dist_pair)`` for :func:`redistribute` and by the
#: string ``"panel_spread"`` for :func:`panel_spread`.  Tests assert routing
#: through it (e.g. that the cholesky/herk trailing chain takes the fused
#: panel-spread path instead of three redistribute calls); clear between
#: measurements with ``REDIST_COUNTS.clear()``.  Counts python-level entry
#: calls, not executed collectives -- jit caching does not hide them.
REDIST_COUNTS: Counter = Counter()


@contextlib.contextmanager
def redist_counts():
    """Scoped redistribute/panel_spread call counting.

    Swaps a fresh Counter in for the module-global :data:`REDIST_COUNTS`
    for the duration of the block and yields it: counts observed inside
    the block accumulate on the yielded Counter (readable both during and
    after the block), and the previous global counter is restored
    untouched on exit -- so counter state cannot leak between tests or
    measurements.  The module-level ``REDIST_COUNTS`` name remains as the
    backward-compatible process-global default for code that does not use
    the context manager (note: ``from ... import REDIST_COUNTS`` binds the
    *current* counter object; prefer this context manager, the
    ``redist_counter`` pytest fixture, or attribute access via the
    module)."""
    global REDIST_COUNTS
    prev = REDIST_COUNTS
    cur: Counter = Counter()
    REDIST_COUNTS = cur
    try:
        yield cur
    finally:
        REDIST_COUNTS = prev


# ---------------------------------------------------------------------
# dist-metadata trace hook (the static comm-plan analyzer's view of the
# engine: elemental_tpu/analysis/ correlates these Python-level records
# with the collectives it finds in the traced jaxpr)
# ---------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RedistRecord:
    """One public-entry redistribution call observed under redist_trace."""
    kind: str            # "redistribute" | "panel_spread"
    src: tuple           # (cdist, rdist) Dist pair of the source
    dst: tuple           # target pair ("panel_spread": the [MC,*]/[*,MR] pair)
    gshape: tuple        # source global shape
    dtype: str
    in_id: int           # id() of the source local array/tracer
    out_ids: tuple       # id() of the produced local array(s)/tracer(s)
    grid_shape: tuple = ()   # (r, c) of the grid (obs ring-byte estimates)
    #: dtype actually moved on the wire (== ``dtype`` unless the entry ran
    #: under a ``comm_precision`` mode -- "bfloat16" / "int8" then)
    wire_dtype: str = ""
    #: route the engine resolved for this entry: "chain" (factored hops,
    #: the default), "direct" (one-shot compiled plan), or "storage" (the
    #: row-permute fast path, whose cross-device motion GSPMD plans)
    path: str = "chain"
    #: collective rounds the resolved route issues (-1 = not computed)
    rounds: int = -1
    #: ring-model bytes received per device by the resolved route
    #: (-1 = not computed); with ``rounds`` this is the "per-round wire
    #: bytes" record of the chosen path
    wire_bytes: int = -1
    #: why a ``path='direct'|'auto'`` request resolved to the chain
    #: ("" = it did not fall back): "noop" (src == dst at equal aligns),
    #: "no_plan" (compile_plan returned None), or "arbitration" (the
    #: measured/ring cost model preferred the chain under 'auto').
    #: Mirrored into the ``redist_fallbacks`` obs counter.
    fallback_reason: str = ""
    # live references keep the ids above unambiguous (no id reuse after GC)
    refs: tuple = dataclasses.field(default=(), repr=False, compare=False)

    @property
    def label(self) -> str:
        # non-redistribute kinds ("panel_spread", "row_permute") label as
        # themselves; dist pairs keep the PATH-INDEPENDENT [src]->[dst]
        # form so comm-plan goldens aggregate identically on either route
        if self.kind != "redistribute":
            return self.kind
        s = f"[{self.src[0].value},{self.src[1].value}]"
        d = f"[{self.dst[0].value},{self.dst[1].value}]"
        return f"{s}->{d}"


_REDIST_TRACE: list | None = None


@contextlib.contextmanager
def redist_trace():
    """Record dist-level metadata for every :func:`redistribute` /
    :func:`panel_spread` entry inside the block.

    Yields the live list of :class:`RedistRecord`; the analyzer uses the
    ``in_id``/``out_ids`` object identities to prove data-flow adjacency
    (a record whose input IS a previous record's untouched output had no
    intervening compute -- the round-trip lint)."""
    global _REDIST_TRACE
    prev = _REDIST_TRACE
    log: list = []
    _REDIST_TRACE = log
    try:
        yield log
    finally:
        _REDIST_TRACE = prev


#: runtime observers (``elemental_tpu.obs.Tracer`` activation registers
#: one): callbacks invoked with every RedistRecord as it happens, whether
#: or not a ``redist_trace`` block is also collecting.
_REDIST_OBSERVERS: list = []


def add_redist_observer(cb) -> callable:
    """Register ``cb(record)`` on every public redistribute/panel_spread
    entry; returns a zero-argument remover (idempotent)."""
    _REDIST_OBSERVERS.append(cb)

    def remove():
        try:
            _REDIST_OBSERVERS.remove(cb)
        except ValueError:
            pass
    return remove


# ---------------------------------------------------------------------
# fault-injection seam (elemental_tpu.resilience, ISSUE 7): a seeded
# FaultPlan installed here corrupts chosen public redistribute /
# panel_spread payloads, so the certified-solve tests can prove each
# corruption class is repaired by escalation or surfaced as a health
# report.  None (the default) is the zero-overhead path.
# ---------------------------------------------------------------------

_FAULT_INJECTOR = None


@contextlib.contextmanager
def fault_injection(plan):
    """Install ``plan`` (a ``resilience.faults.FaultPlan``, or anything
    with ``apply(target, outputs) -> outputs``) as the engine's fault
    injector for the block; the previous injector is restored on exit.
    Every public :func:`redistribute` / :func:`panel_spread` entry routes
    its output local array(s) through ``plan.apply`` before returning."""
    global _FAULT_INJECTOR
    prev = _FAULT_INJECTOR
    _FAULT_INJECTOR = plan
    try:
        yield plan
    finally:
        _FAULT_INJECTOR = prev


def set_fault_step(step) -> None:
    """Announce the current driver panel step to the installed fault
    injector (``None`` = leaving the step scope).  Gates
    ``FaultSpec(window=...)`` rules (ISSUE 11): the ABFT-guarded
    factorizations call this at every panel-transaction boundary so
    chaos tests can corrupt a chosen step deterministically.  A no-op --
    zero traced operations -- when no injector is installed or the
    injector has no ``set_step``."""
    inj = _FAULT_INJECTOR
    if inj is not None:
        f = getattr(inj, "set_step", None)
        if f is not None:
            f(step)


def apply_fault(target: str, outputs: tuple) -> tuple:
    """Route eager kernel outputs through the installed fault injector;
    identity (and zero-overhead) when none is installed.

    The engine corrupts its own ``redistribute``/``panel_spread`` payloads
    internally; this is the seam OTHER layers use for the ``'compute'``
    fault target (ISSUE 9) -- the lu/cholesky/qr panel kernels and the
    serve executor's batched solve route their local outputs through it,
    so chaos tests cover soft errors in local math with the same seeded
    bit-identical replay guarantee as the collective targets."""
    if _FAULT_INJECTOR is None:
        return tuple(outputs)
    return tuple(_FAULT_INJECTOR.apply(target, tuple(outputs)))


def _trace_record(kind, src, dst, gshape, dtype, objs_in, objs_out,
                  grid_shape=(), wire_dtype=None, path="chain", rounds=-1,
                  wire_bytes=-1, fallback_reason="", observers_only=False):
    """Build + publish one RedistRecord.  ``observers_only`` skips the
    ``redist_trace`` list (used by the row-permute fast path: the obs
    tracer must see its wire traffic, but the comm-plan goldens aggregate
    ``redist_trace`` records and GSPMD-planned motion has no explicit
    collective rounds to pin)."""
    if _REDIST_TRACE is None and not _REDIST_OBSERVERS:
        return
    rec = RedistRecord(
        kind=kind, src=tuple(src), dst=tuple(dst), gshape=tuple(gshape),
        dtype=str(dtype), in_id=id(objs_in),
        out_ids=tuple(id(o) for o in objs_out), grid_shape=tuple(grid_shape),
        wire_dtype=str(wire_dtype or dtype), path=path, rounds=rounds,
        wire_bytes=wire_bytes, fallback_reason=fallback_reason,
        refs=(objs_in,) + tuple(objs_out))
    if _REDIST_TRACE is not None and not observers_only:
        _REDIST_TRACE.append(rec)
    for cb in tuple(_REDIST_OBSERVERS):
        cb(rec)


# ---------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------

def _pad_dim(x, dim: int, target: int):
    cur = x.shape[dim]
    if cur == target:
        return x
    pads = [(0, 0)] * x.ndim
    pads[dim] = (0, target - cur)
    return jnp.pad(x, pads)


def _count_relayout(name: str, block, dtype, dim: int):
    """One ``redist_unpack`` / ``redist_filter`` tick, at trace time:
    ``impl="tiled"`` when the blocks are whole (8, 128) tiles of a 4-byte
    dtype, so that nothing the compiler moves is padded; ``"generic"`` for
    ragged, narrow or other-width blocks, which run the same formula on
    padded tiles.  ``dim`` is 1 for the minor (lane) dimension of the
    block, else 0."""
    whole = (dtype.itemsize == 4 and len(block) >= 2
             and block[-1] % 128 == 0 and block[-2] % 8 == 0)
    _metrics.inc(name, impl="tiled" if whole else "generic",
                 dim=int(dim == len(block) - 1))


def _interleave(g, dim: int):
    """The local unpack after a gather over a cyclic dimension: ``g`` holds
    ``S`` rank-ordered blocks, shape ``(S, ...)``; the result has
    ``shape[dim] * S`` along block dimension ``dim``, index
    ``i = iLoc*S + s``.  Pure data movement (bit-exact, non-finite values
    included).

    ONE cyclic dimension per call.  The TPU compiler lays this out in
    whole tiles whichever dimension it is (a minor-dimension interleave
    becomes transpose, row interleave, transpose), but a single transpose
    that interleaves rows AND columns leaves it an intermediate whose minor
    dimension is ``S``, padded to 128 lanes: 1 GiB and 2.4 ms for a 16 MB
    block on a v5e (PERF.md 6, PR 29).  A 2-D unpack is two calls.

    Counted at trace time in ``redist_unpack{impl,dim}``
    (:func:`_count_relayout`) by the ``S`` blocks it is given."""
    S = g.shape[0]
    if S == 1:
        return g[0]
    shape = list(g.shape[1:])
    _count_relayout("redist_unpack", shape, g.dtype, dim)
    shape[dim] *= S
    return jnp.moveaxis(g, 0, dim + 1).reshape(shape)


def _deinterleave(x, dim: int, S: int, shift):
    """The mirror of :func:`_interleave`: the cyclic slice
    ``i = iLoc*S + shift`` of dimension ``dim`` (extent a multiple of
    ``S``; ``shift`` may be traced).  Counted at trace time in
    ``redist_filter{impl,dim}`` by the block it returns.

    The operand is taken behind ``lax.optimization_barrier``, whichever
    dimension is sliced.  Alone, the reshape below compiles to whole tiles
    (a lane slice becomes transpose, row slice, transpose); merged with a
    producer's reshape, an :func:`_interleave`'s above all, it becomes one
    reshape whose minor dimension is ``S``, padded to 128 lanes.  Two cases
    paid for it on a v5e: ``panel_spread``'s row interleave feeding a LANE
    slice (7.5 GB for a 60 MB panel, and the 2x2 Cholesky cell no longer
    fitted: PERF.md 6, PR 29), and the [STAR,VR] -> [STAR,MR] partial
    gather's lane interleave feeding the ROW slice of [STAR,MR] -> [MC,MR]
    in the LU step (a 4.29 GB temporary for a 67 MB block, written by one
    op and read by the next, 16 ms of a step: PERF.md 6, PR 32)."""
    if S == 1:
        return x
    block = list(x.shape)
    block[dim] //= S
    _count_relayout("redist_filter", block, x.dtype, dim)
    split = block[: dim + 1] + [S] + block[dim + 1 :]     # (..., l_out, S, ...)
    return lax.dynamic_index_in_dim(
        lax.optimization_barrier(x).reshape(split), shift, axis=dim + 1,
        keepdims=False)


def _gather_dim(x, dim: int, d: Dist, align: int, extent: int, r: int, c: int):
    """Rebuild the full (true-extent) dimension on every device."""
    S = r * c if d is MD else dist_stride(d, r, c)
    if S == 1:
        with _part("unpack"):
            return lax.slice_in_dim(x, 0, extent, axis=dim)
    if d is MD:
        # p slot-ranges of length l gathered mc-major, then the static
        # slot permutation rebuilds global order (copy:: for [MD,*])
        with _part("wire"):
            g = lax.all_gather(x, ("mc", "mr"), axis=0)   # (p, l, ...)
        with _part("unpack"):
            shape = list(x.shape)
            shape[dim] = x.shape[dim] * r * c
            g = jnp.moveaxis(g, 0, dim)
            gflat = g.reshape(shape)                      # slot-major flat
            idx = jnp.asarray(md_slot_of_global(r, c, extent))
            return jnp.take(gflat, idx, axis=dim)
    with _part("wire"):
        g = lax.all_gather(x, gather_axes(d), axis=0)    # (S, ...) rank-ordered
    with _part("unpack"):
        if align:
            g = jnp.roll(g, -align, axis=0)               # block s <- shift s
        return lax.slice_in_dim(_interleave(g, dim), 0, extent, axis=dim)


def _filter_md(x, dim: int, extent: int, r: int, c: int):
    """Replicated dim -> this device's MD slot range: k = k0 + t*lcm for
    owners (k0 = rank_of(MD) < lcm), all-zero slots for devices outside
    the diagonal comm (sentinel k0 == lcm maps every index out of range)."""
    L = dist_stride(MD, r, c)
    l = ix.max_local_length(extent, L)
    k0 = rank_of(MD, r, c)
    gi = jnp.arange(l) * L + k0
    gi = jnp.where((k0 < L) & (gi < extent), gi, extent)
    return jnp.take(x, gi, axis=dim, mode="fill", fill_value=0)


def _filter_dim(x, dim: int, S: int, shift, l_out: int):
    """Select this device's cyclic slice of a replicated dimension."""
    if S == 1:
        return _pad_dim(x, dim, l_out)
    return _deinterleave(_pad_dim(x, dim, S * l_out), dim, S, shift)


def _partial_gather_dim(x, dim: int, axes, nblocks: int, l_out: int):
    """V* -> M* ladder: gather ``nblocks`` interleaved sub-blocks.

    cf. ``copy::PartialColAllGather``: the devices sharing this dimension's
    coarse rank gather their fine-grained cyclic blocks; interleaving them
    yields the coarse-cyclic local block.
    """
    if nblocks == 1:                    # degenerate: nothing to exchange
        with _part("unpack"):
            return lax.slice_in_dim(x, 0, l_out, axis=dim)
    with _part("wire"):
        g = lax.all_gather(x, axes, axis=0)               # (nblocks, l_in, ...)
    with _part("unpack"):
        return lax.slice_in_dim(_interleave(g, dim), 0, l_out, axis=dim)


def _partial_filter_dim(x, dim: int, nblocks: int, sub_rank, l_out: int):
    """M* -> V* ladder: pure-local selection of the finer cyclic slice
    (cf. ``copy::PartialColFilter``)."""
    return _deinterleave(_pad_dim(x, dim, nblocks * l_out), dim, nblocks,
                         sub_rank)


# ---------------------------------------------------------------------
# fused M <-> V conversions (one all_to_all; the reference's
# copy::Exchange-class kernels, mn/p volume instead of the mn/r gather)
# ---------------------------------------------------------------------

def _fused_to_v(A: DistMatrix) -> DistMatrix:
    """[MC,MR] -> [VC,STAR] or [MR,MC] -> [VR,STAR]: the V dist refines the
    row dist, so ONE all_to_all over the column axis both refines the rows
    and rebuilds the full column extent (each peer contributes its cyclic
    column slice; the interleave positions land exactly at the natural
    global order)."""
    g = A.grid
    r, c = g.height, g.width
    p = r * c
    m, n = A.gshape
    if A.dist == (MC, MR):
        ax, n_other, dst = "mr", c, VC
    else:                                   # (MR, MC)
        ax, n_other, dst = "mc", r, VR
    lt = ix.max_local_length(m, p)
    with _part("pack" if n_other > 1 else "unpack"):
        x = _pad_dim(A.local, 0, n_other * lt)
        lc = x.shape[1]
        x3 = x.reshape(lt, n_other, lc)     # row t = w*n_other + g
    with _part("wire"):
        y = x3 if n_other == 1 \
            else lax.all_to_all(x3, ax, split_axis=1, concat_axis=1)
    with _part("unpack"):
        z = _interleave(jnp.moveaxis(y, 1, 0), 1)  # col j = jLoc*n_other + g
        z = lax.slice_in_dim(z, 0, n, axis=1)
        v = rank_of(dst, r, c)
        gi = jnp.arange(lt) * p + v
        z = jnp.where((gi < m)[:, None], z, 0)
    return DistMatrix(z, A.gshape, dst, STAR, 0, 0, g)


def _fused_from_v(A: DistMatrix) -> DistMatrix:
    """[VC,STAR] -> [MC,MR] or [VR,STAR] -> [MR,MC] (inverse of
    :func:`_fused_to_v`; one all_to_all over the target column axis)."""
    g = A.grid
    r, c = g.height, g.width
    p = r * c
    m, n = A.gshape
    if A.cdist is VC:
        ax, n_other, dst = "mr", c, (MC, MR)
        S_row = r
    else:                                   # VR
        ax, n_other, dst = "mc", r, (MR, MC)
        S_row = c
    lp = A.local.shape[0]                   # ceil(m/p)
    lcd = ix.max_local_length(n, n_other)
    with _part("pack" if n_other > 1 else "unpack"):
        x = _pad_dim(A.local, 1, n_other * lcd)
        x3 = x.reshape(lp, lcd, n_other)    # col j = u*n_other + s
    with _part("wire"):
        y = x3 if n_other == 1 \
            else lax.all_to_all(x3, ax, split_axis=2, concat_axis=2)
    with _part("unpack"):
        z = _interleave(jnp.moveaxis(y, 2, 0), 0)  # row i = iLoc*n_other + s
        lr = ix.max_local_length(m, S_row)
        z = lax.slice_in_dim(z, 0, lr, axis=0)
        q_row = rank_of(dst[0], r, c)
        gi = jnp.arange(lr) * S_row + q_row
        q_col = rank_of(dst[1], r, c)
        gj = jnp.arange(lcd) * n_other + q_col
        z = jnp.where((gi < m)[:, None] & (gj < n)[None, :], z, 0)
    return DistMatrix(z, A.gshape, dst[0], dst[1], 0, 0, g)


def _t_meta(A: DistMatrix) -> DistMatrix:
    """Local transpose + swapped metadata (free; used to reuse the fused
    row-kernels for the [STAR,V] column forms)."""
    m, n = A.gshape
    return DistMatrix(A.local.T, (n, m), A.rdist, A.cdist,
                      A.ralign, A.calign, A.grid)


def _interleave_2d(G, dist):
    """``G[mc, mr, il, jl]`` (the blocks of an [MC,MR] or [MR,MC] matrix) ->
    the global block: columns, then rows, one :func:`_interleave` each."""
    if dist == (MC, MR):
        # global (i, j) = (il*r + mc, jl*c + mr)
        G = jnp.moveaxis(G, 1, 0)
    # else (MR, MC): global (i, j) = (il*c + mr, jl*r + mc)
    return _interleave(_interleave(G, 2), 0)


def _fused_to_star_star(A: DistMatrix) -> DistMatrix | None:
    """[MC,MR] / [MR,MC] -> [STAR,STAR] in ONE all_gather over the flattened
    ('mc','mr') axis + a static interleave, instead of the generic route's
    two sequential per-dim gathers with an mn/r intermediate (the panel
    gather of the blocked factorizations -- e.g. the LU look-ahead strip --
    is the hot caller).  Falls back (None) on 1-D grids, where the generic
    path is already a single collective."""
    g = A.grid
    r, c = g.height, g.width
    if r == 1 or c == 1:
        return None
    m, n = A.gshape
    x = A.local
    lr, lc = x.shape
    with _part("wire"):
        gx = lax.all_gather(x, ("mc", "mr"), axis=0)  # (r*c, lr, lc), mc-major
    with _part("unpack"):
        full = lax.slice(_interleave_2d(gx.reshape(r, c, lr, lc), A.dist),
                         (0, 0), (m, n))
    return DistMatrix(full, A.gshape, STAR, STAR, 0, 0, g)


def _fused_dispatch(A: DistMatrix, dst) -> DistMatrix | None:
    src = A.dist
    if src in ((MC, MR), (MR, MC)) and dst == (STAR, STAR):
        return _fused_to_star_star(A)
    if src == (MC, MR) and dst == (VC, STAR):
        return _fused_to_v(A)
    if src == (MR, MC) and dst == (VR, STAR):
        return _fused_to_v(A)
    if src == (VC, STAR) and dst == (MC, MR):
        return _fused_from_v(A)
    if src == (VR, STAR) and dst == (MR, MC):
        return _fused_from_v(A)
    # transposed (column) forms ride the row kernels on the local transpose
    if (src, dst) in (((MC, MR), (STAR, VR)), ((MR, MC), (STAR, VC))):
        return _on_transpose(_fused_to_v, A)
    if (src, dst) in (((STAR, VR), (MC, MR)), ((STAR, VC), (MR, MC))):
        return _on_transpose(_fused_from_v, A)
    return None


def _on_transpose(kernel, A: DistMatrix) -> DistMatrix:
    """A fused row kernel on the local transpose, transposed back."""
    with _part("pack"):
        At = _t_meta(A)
    out = kernel(At)
    with _part("unpack"):
        return _t_meta(out)


# ---------------------------------------------------------------------
# re-alignment (pure ppermute rotation per dim)
# ---------------------------------------------------------------------

def _realign(A: DistMatrix, calign: int, ralign: int) -> DistMatrix:
    """Change alignments in place: owner of index i moves from (i+a)%S to
    (i+a')%S -- a wholesale device ROTATION per dim, no local rearrangement
    (the reference's aligned-copy SendRecv)."""
    from .interior import _rot_perm
    g = A.grid
    r, c = g.height, g.width
    x = A.local
    for dim, d, a_old, a_new in ((0, A.cdist, A.calign, calign),
                                 (1, A.rdist, A.ralign, ralign)):
        S = dist_stride(d, r, c)
        if S == 1 or a_old == a_new:
            continue
        axes, perm = _rot_perm(d, (a_old - a_new) % S, r, c)
        with _part("wire"):
            x = lax.ppermute(x, axes, perm)
    return DistMatrix(x, A.gshape, A.cdist, A.rdist, calign, ralign, A.grid)


# ---------------------------------------------------------------------
# whole-matrix operations (inside shard_map)
# ---------------------------------------------------------------------

def to_star_star(A: DistMatrix) -> DistMatrix:
    g = A.grid
    r, c = g.height, g.width
    xg = _gather_dim(A.local, 0, A.cdist, A.calign, A.gshape[0], r, c)
    xg = _gather_dim(xg, 1, A.rdist, A.ralign, A.gshape[1], r, c)
    return DistMatrix(xg, A.gshape, STAR, STAR, 0, 0, g)


def _from_star_star(xg, gshape, cdist, rdist, calign, ralign, grid) -> DistMatrix:
    r, c = grid.height, grid.width
    Sc, Sr = dist_stride(cdist, r, c), dist_stride(rdist, r, c)
    lr = ix.max_local_length(gshape[0], Sc)
    lc = ix.max_local_length(gshape[1], Sr)
    with _part("unpack"):
        if cdist is MD:
            loc = _filter_md(xg, 0, gshape[0], r, c)
        else:
            loc = _filter_dim(xg, 0, Sc,
                              ix.shift(rank_of(cdist, r, c), calign, Sc), lr)
        if rdist is MD:
            loc = _filter_md(loc, 1, gshape[1], r, c)
        else:
            loc = _filter_dim(loc, 1, Sr,
                              ix.shift(rank_of(rdist, r, c), ralign, Sr), lc)
        # zero the padding tail (rows whose global index >= extent)
        loc = _zero_padding(loc, gshape, cdist, rdist, calign, ralign, grid)
    return DistMatrix(loc, gshape, cdist, rdist, calign, ralign, grid)


def _zero_padding(loc, gshape, cdist, rdist, calign, ralign, grid) -> jnp.ndarray:
    """Enforce the padding-is-zero invariant on a freshly filtered block."""
    r, c = grid.height, grid.width
    Sc, Sr = dist_stride(cdist, r, c), dist_stride(rdist, r, c)
    out = loc
    if cdist is MD or rdist is MD:
        return out        # _filter_md zero-fills everything out of range
    if loc.shape[0] * Sc != gshape[0]:
        shift = ix.shift(rank_of(cdist, r, c), calign, Sc)
        gi = jnp.arange(loc.shape[0]) * Sc + shift
        out = jnp.where((gi < gshape[0])[:, None], out, 0)
    if loc.shape[1] * Sr != gshape[1]:
        shift = ix.shift(rank_of(rdist, r, c), ralign, Sr)
        gj = jnp.arange(loc.shape[1]) * Sr + shift
        out = jnp.where((gj < gshape[1])[None, :], out, 0)
    return out


def _zero_aligned(A: DistMatrix) -> bool:
    return A.calign == 0 and A.ralign == 0


def to_dist(A: DistMatrix, cdist: Dist, rdist: Dist,
            calign: int = 0, ralign: int = 0) -> DistMatrix:
    """``B[cdist,rdist] = A`` -- the redistribution dispatch (inside shard_map)."""
    _check_pair(cdist, rdist)
    g = A.grid
    src = (A.cdist, A.rdist)
    dst = (cdist, rdist)

    if src == dst and (A.calign, A.ralign) == (calign, ralign):
        return A

    # MD's owner map is not a nested axis order: every conversion rides
    # the MD-aware gather/filter through [STAR,STAR] (copy::Gather/
    # Scatter class; the hot MD op -- diagonal extraction -- is the
    # pure-local path in level1.get_diagonal, not a redistribution)
    if MD in (A.cdist, A.rdist, cdist, rdist):
        if (calign, ralign) != (0, 0):
            raise ValueError("MD redistributions require zero alignments")
        ss = to_star_star(A)
        return _from_star_star(ss.local, A.gshape, cdist, rdist, 0, 0, g)

    # alignment-only change: a pure per-dim device rotation
    if src == dst:
        return _realign(A, calign, ralign)
    # misaligned source / aligned target: rotate to/from zero alignment so
    # every dist change runs on the zero-aligned fast paths (this removes
    # the [STAR,STAR] fallback from all aligned redistributions)
    if not _zero_aligned(A):
        return to_dist(_realign(A, 0, 0), cdist, rdist, calign, ralign)
    if (calign, ralign) != (0, 0):
        out = to_dist(A, cdist, rdist, 0, 0)
        return _realign(out, calign, ralign)

    # ---- fast paths (zero alignments) --------------------------------
    out = _fused_dispatch(A, dst)
    if out is not None:
        return out
    # pure row-dim change, column dist untouched
    if A.cdist is cdist:
        out = _rowdim_change(A, rdist)
        if out is not None:
            return out
    # pure col-dim change, row dist untouched
    if A.rdist is rdist:
        out = _coldim_change(A, cdist)
        if out is not None:
            return out
    # composite chains of fast single-dim hops
    chain = _CHAINS.get((src, dst))
    if chain is not None:
        out = A
        for hop in chain:
            out = to_dist(out, *hop)
        return out

    # ---- generic fallback: through [STAR,STAR] ------------------------
    ss = to_star_star(A)
    return _from_star_star(ss.local, A.gshape, cdist, rdist, calign, ralign, g)


#: Multi-hop routes for the pairs without a dedicated kernel.  Every route
#: now rides the FUSED all_to_all M<->V conversions (:func:`_fused_to_v` /
#: :func:`_fused_from_v`, mn/p volume per hop) plus the [VC]<->[VR]
#: ppermute -- the reference's ``copy::Exchange`` family
#: (``src/blas_like/level1/Copy/Exchange.hpp``); the old gather+filter
#: first hops (mn/r volume) are gone.
_CHAINS = {
    # transpose-pair exchange: fused demote, ppermute, fused promote
    ((MC, MR), (MR, MC)): ((VC, STAR), (VR, STAR), (MR, MC)),
    ((MR, MC), (MC, MR)): ((VR, STAR), (VC, STAR), (MC, MR)),
    # remaining 1-D cyclic forms (the directly-fused ones dispatch earlier)
    ((MC, MR), (VR, STAR)): ((VC, STAR), (VR, STAR)),
    ((MC, MR), (STAR, VC)): ((STAR, VR), (STAR, VC)),
    ((VR, STAR), (MC, MR)): ((VC, STAR), (MC, MR)),
    ((STAR, VC), (MC, MR)): ((STAR, VR), (MC, MR)),
    ((MR, MC), (VC, STAR)): ((VR, STAR), (VC, STAR)),
    ((MR, MC), (STAR, VR)): ((STAR, VC), (STAR, VR)),
    ((VC, STAR), (MR, MC)): ((VR, STAR), (MR, MC)),
    ((STAR, VR), (MR, MC)): ((STAR, VC), (MR, MC)),
    # cross-dim single-replicated targets (SUMMA panel moves)
    ((MC, MR), (MR, STAR)): ((VC, STAR), (VR, STAR), (MR, STAR)),
    ((MC, MR), (STAR, MC)): ((STAR, VR), (STAR, VC), (STAR, MC)),
    ((MR, MC), (MC, STAR)): ((VR, STAR), (VC, STAR), (MC, STAR)),
    ((MR, MC), (STAR, MR)): ((STAR, VC), (STAR, VR), (STAR, MR)),
    ((MR, STAR), (MC, MR)): ((VR, STAR), (VC, STAR), (MC, MR)),
    ((STAR, MC), (MC, MR)): ((STAR, VC), (STAR, VR), (MC, MR)),
    ((MC, STAR), (MR, MC)): ((VC, STAR), (VR, STAR), (MR, MC)),
    ((STAR, MR), (MR, MC)): ((STAR, VR), (STAR, VC), (MR, MC)),
    # V-form to the opposite M-form (Cholesky/Herk panel adjoint chains)
    ((VC, STAR), (MR, STAR)): ((VR, STAR), (MR, STAR)),
    ((VR, STAR), (MC, STAR)): ((VC, STAR), (MC, STAR)),
    ((STAR, VC), (STAR, MR)): ((STAR, VR), (STAR, MR)),
    ((STAR, VR), (STAR, MC)): ((STAR, VC), (STAR, MC)),
}


def _rowdim_change(A: DistMatrix, rdist: Dist) -> DistMatrix | None:
    """Change only the row (second-dim) distribution; col dist fixed.

    Legality of the source/target pairs guarantees the axes involved are
    disjoint from the column distribution's axes.
    """
    g = A.grid
    r, c = g.height, g.width
    m, n = A.gshape
    src = A.rdist
    if src is rdist:
        return A
    # replicated -> distributed: local filter
    if src is STAR:
        Sr = dist_stride(rdist, r, c)
        lc = ix.max_local_length(n, Sr)
        with _part("unpack"):
            loc = _filter_dim(A.local, 1, Sr,
                              ix.shift(rank_of(rdist, r, c), 0, Sr), lc)
        return DistMatrix(loc, A.gshape, A.cdist, rdist, A.calign, 0, g)
    # distributed -> replicated: gather
    if rdist is STAR:
        loc = _gather_dim(A.local, 1, src, A.ralign, n, r, c)
        return DistMatrix(loc, A.gshape, A.cdist, STAR, A.calign, 0, g)
    # V* <-> M* partial ladder on dim 1
    out = _partial_ladder(A, dim=1, src=src, dst=rdist)
    if out is not None:
        return out
    return None


def _coldim_change(A: DistMatrix, cdist: Dist) -> DistMatrix | None:
    g = A.grid
    r, c = g.height, g.width
    m, n = A.gshape
    src = A.cdist
    if src is cdist:
        return A
    if src is STAR:
        Sc = dist_stride(cdist, r, c)
        lr = ix.max_local_length(m, Sc)
        with _part("unpack"):
            loc = _filter_dim(A.local, 0, Sc,
                              ix.shift(rank_of(cdist, r, c), 0, Sc), lr)
        return DistMatrix(loc, A.gshape, cdist, A.rdist, 0, A.ralign, g)
    if cdist is STAR:
        loc = _gather_dim(A.local, 0, src, A.calign, m, r, c)
        return DistMatrix(loc, A.gshape, STAR, A.rdist, 0, A.ralign, g)
    out = _partial_ladder(A, dim=0, src=src, dst=cdist)
    if out is not None:
        return out
    return None


def _partial_ladder(A: DistMatrix, dim: int, src: Dist, dst: Dist) -> DistMatrix | None:
    """[VC,*]<->[MC,*] / [VR,*]<->[MR,*] partial gathers/filters (zero align).

    VC refines MC (q_vc = mc + r*mr), VR refines MR (q_vr = mr + c*mc):
      * V -> M: all_gather the co-axis, interleave      (PartialColAllGather)
      * M -> V: pure-local cyclic sub-selection         (PartialColFilter)
    """
    g = A.grid
    r, c = g.height, g.width
    p = r * c
    extent = A.gshape[dim]
    if (src, dst) == (VC, MC) or (src, dst) == (VR, MR):
        axes = ("mr",) if src is VC else ("mc",)
        nblocks = c if src is VC else r
        coarse = r if src is VC else c
        l_out = ix.max_local_length(extent, coarse)
        loc = _partial_gather_dim(A.local, dim, axes, nblocks, l_out)
        return _retag(A, dim, dst, loc)
    if (src, dst) == (MC, VC) or (src, dst) == (MR, VR):
        nblocks = c if dst is VC else r
        l_out = ix.max_local_length(extent, p)
        with _part("unpack"):
            sub = lax.axis_index("mr") if dst is VC else lax.axis_index("mc")
            loc = _partial_filter_dim(A.local, dim, nblocks, sub, l_out)
        return _retag(A, dim, dst, loc)
    if {src, dst} == {VC, VR}:
        loc = _vc_vr_permute(A.local, src, r, c)
        return _retag(A, dim, dst, loc)
    return None


def _vc_vr_permute(x, src: Dist, r: int, c: int):
    """[VC,*] <-> [VR,*]: a pure block permutation between the two 1-D rank
    orderings (the reference does this with a single pairwise SendRecv --
    ``copy::Exchange`` inside ``src/blas_like/level1/Copy/``); here one
    ``lax.ppermute`` over the flattened ('mc','mr') axis (linear index
    mc*c + mr, first name major).

    VC rank v lives on device (mc=v%r, mr=v//r); VR rank v on
    (mc=v//c, mr=v%c).  The residue class {i : i%p == v} moves wholesale
    from its VC owner to its VR owner (or back).
    """
    p = r * c
    if p == 1 or r == 1 or c == 1:
        return x
    # linear device index under ('mc','mr') = mc*c + mr; note VR rank v lives
    # on (mc=v//c, mr=v%c), i.e. the linear device index IS the VR rank.
    vc_dev = [(v % r) * c + v // r for v in range(p)]   # device holding VC rank v
    if src is VC:
        perm = [(vc_dev[v], v) for v in range(p)]
    else:
        perm = [(v, vc_dev[v]) for v in range(p)]
    with _part("wire"):
        return lax.ppermute(x, ("mc", "mr"), perm)


def _retag(A: DistMatrix, dim: int, d: Dist, loc) -> DistMatrix:
    if dim == 0:
        return DistMatrix(loc, A.gshape, d, A.rdist, 0, A.ralign, A.grid)
    return DistMatrix(loc, A.gshape, A.cdist, d, A.calign, 0, A.grid)


# ---------------------------------------------------------------------
# one-shot direct path (ISSUE 12 -- the COSTA plan compiler in .plan):
# static chain-cost mirror, the shard_map executor for a compiled
# RedistPlan, and the per-call chain-vs-direct arbitration
# ---------------------------------------------------------------------

def _fused_steps(src, dst, r, c):
    """Steps of the fused fast paths of :func:`_fused_dispatch`, as
    (kind, participants, moving-block dist pair) tuples -- None when no
    fused kernel dispatches (mirrors its conditions exactly)."""
    if src in ((MC, MR), (MR, MC)) and dst == (STAR, STAR):
        if r > 1 and c > 1:
            return [("ag", r * c, src)]
        return None                         # 1-D grid: generic route
    fused_v = {((MC, MR), (VC, STAR)), ((VC, STAR), (MC, MR)),
               ((MR, MC), (VR, STAR)), ((VR, STAR), (MR, MC)),
               ((MC, MR), (STAR, VR)), ((STAR, VR), (MC, MR)),
               ((MR, MC), (STAR, VC)), ((STAR, VC), (MR, MC))}
    if (src, dst) in fused_v:
        # the fused M<->V kernels a2a over the axis the V dist refines
        # ALONG: c participants when VC is the V endpoint, r when VR
        vs = [d for pair in (src, dst) for d in pair if d in (VC, VR)]
        return [("a2a", c if vs[0] is VC else r, src)]
    return None


def _dim_steps(pair, dim, new, r, c):
    """Steps of a single-dim change (:func:`_rowdim_change` /
    :func:`_coldim_change` + the partial ladder), or None (no fast path)."""
    src_d = pair[dim]
    p = r * c
    if src_d is new:
        return []
    if src_d is STAR:
        return [("local", 1, pair)]
    if new is STAR:
        S = dist_stride(src_d, r, c)
        return [("ag", S, pair)] if S > 1 else [("local", 1, pair)]
    if (src_d, new) in ((VC, MC), (VR, MR)):
        nb = c if src_d is VC else r
        return [("ag", nb, pair)] if nb > 1 else [("local", 1, pair)]
    if (src_d, new) in ((MC, VC), (MR, VR)):
        return [("local", 1, pair)]
    if {src_d, new} == {VC, VR}:
        if p == 1 or r == 1 or c == 1:
            return [("local", 1, pair)]
        return [("ppermute", p, pair)]
    return None


def _chain_steps(src, dst, r, c):
    """Static mirror of :func:`to_dist`'s zero-aligned dispatch: the
    ordered (kind, participants, block pair) collective steps the chained
    route runs for ``src -> dst``.  Purely metadata -- nothing traces."""
    if src == dst:
        return []
    steps = _fused_steps(src, dst, r, c)
    if steps is not None:
        return steps
    if src[0] is dst[0]:
        steps = _dim_steps(src, 1, dst[1], r, c)
        if steps is not None:
            return steps
    if src[1] is dst[1]:
        steps = _dim_steps(src, 0, dst[0], r, c)
        if steps is not None:
            return steps
    route = _CHAINS.get((src, dst))
    if route is not None:
        steps, cur = [], src
        for hop in route:
            steps += _chain_steps(cur, hop, r, c)
            cur = hop
        return steps
    # generic fallback: per-dim gathers through [STAR,STAR], local filter
    steps = []
    for dim, pair in ((0, src), (1, (STAR, src[1]))):
        if pair[dim] is MD:
            steps.append(("ag", r * c, pair))
        elif dist_stride(pair[dim], r, c) > 1:
            steps.append(("ag", dist_stride(pair[dim], r, c), pair))
    return steps


@lru_cache(maxsize=None)
def chain_cost(src, dst, gshape, grid_shape, itemsize):
    """(collective_rounds, ring-model bytes received per device) of the
    CHAINED route for a zero-aligned ``src -> dst`` -- the comparison
    the direct plan is arbitrated against (and the payload of the EL002
    rewrite hint)."""
    src, dst = tuple(src), tuple(dst)
    r, c = grid_shape
    m, n = gshape
    if src == dst or r * c == 1:
        return 0, 0
    rounds, total = 0, 0
    for kind, S, pair in _chain_steps(src, dst, r, c):
        if kind == "local" or S <= 1:
            continue
        b = (itemsize * ix.max_local_length(m, dist_stride(pair[0], r, c))
             * ix.max_local_length(n, dist_stride(pair[1], r, c)))
        rounds += 1
        if kind == "ag":
            total += b * (S - 1)
        elif kind == "a2a":
            total += b * (S - 1) // S
        else:                                  # ppermute
            total += b
    return rounds, total


def direct_plan_for(A: DistMatrix, cdist: Dist, rdist: Dist,
                    calign: int = 0, ralign: int = 0):
    """The compiled one-shot plan for this redistribution (alignments
    included since phase 2), or None when no plan applies (a no-op, or
    an MD endpoint at nonzero alignments -- which ``to_dist`` rejects)."""
    return compile_plan(A.dist, (cdist, rdist), A.gshape,
                        (A.grid.height, A.grid.width),
                        (A.calign, A.ralign), (calign, ralign))


def _machine_terms(grid_shape=None):
    """(latency_s, bw_bytes_per_s) for the running backend.

    Measured ``redist_constants/v1`` recorded by ``perf.redist_bench
    --record`` for this (grid, backend) take precedence over the static
    :mod:`..tune.cost_model` ring model.  A backend the model has no row
    for raises: there is no default machine."""
    from ..tune.cache import load_redist_constants
    from ..tune.cost_model import machine_for
    backend = jax.default_backend()
    if grid_shape is not None:
        doc = load_redist_constants(tuple(grid_shape), backend)
        if doc is not None:
            return float(doc["alpha_s"]), float(doc["bw_bytes_per_s"])
    mm = machine_for(backend)
    return mm.latency_s, mm.bw_bytes_per_s


def _direct_wins(plan, gshape, itemsize) -> bool:
    """``path='auto'`` arbitration: alpha-beta (latency x rounds +
    bytes / bandwidth) comparison of the one-shot plan against the
    chained route, using the measured per-(grid, backend) constants when
    ``redist_bench --record`` has written them; ties go to the chain
    (the bit-identical default)."""
    rounds_c, bytes_c = chain_cost(plan.src, plan.dst, gshape,
                                   plan.grid_shape, itemsize)
    if rounds_c == 0:
        return False
    lat, bw = _machine_terms(plan.grid_shape)
    t_direct = lat * plan.rounds + plan.wire_bytes(itemsize) / bw
    t_chain = lat * rounds_c + bytes_c / bw
    return t_direct < t_chain


def _direct_exec(x, plan, wire, dt):
    """Execute a compiled RedistPlan inside shard_map: static-map gather
    -> one collective (or none) -> static-map scatter onto zeros.

    The (p, K, R)/(p, K, C) tables become jaxpr constants; each device
    selects its row by ``axis_index``.  Sentinel indices (== the local
    extent) mask to zero on the gather and drop on the scatter, which
    keeps the padding-is-zero storage invariant without data-dependent
    shapes.  ``wire='int8'`` block-scale-packs each slot (vmap of the
    :mod:`.quantize` codec) so the ONE collective moves int8; bf16 is
    cast by the caller around this function."""
    r, c = plan.grid_shape
    R, C = plan.slot_shape
    q8 = wire == "int8" and plan.kind != "local"
    with _part("unpack" if plan.kind == "local" else "pack"):
        dev = lax.axis_index("mc") * c + lax.axis_index("mr")
        sr = jnp.take(jnp.asarray(plan.send_rows), dev, axis=0)     # (K, R)
        sc = jnp.take(jnp.asarray(plan.send_cols), dev, axis=0)     # (K, C)
        lr_s, lc_s = plan.src_local
        ok = (sr < lr_s)[:, :, None] & (sc < lc_s)[:, None, :]
        vals = x[jnp.clip(sr, 0, lr_s - 1)[:, :, None],
                 jnp.clip(sc, 0, lc_s - 1)[:, None, :]]
        vals = jnp.where(ok, vals, 0)                           # (K, R, C)
        if q8:
            vals = jax.vmap(lambda s: q8_pack(s, QUANT_TILE))(vals)
    with _part("wire"):
        if plan.kind == "a2a":
            # ragged subgroup a2a: the plan's equal-size participant groups
            # (or None for the full comm product); the K* slots are
            # addressed by GROUP position, which the remapped index tables
            # encode
            gg = [list(g) for g in plan.groups] if plan.groups else None
            recv = lax.all_to_all(vals, plan.comm_axes, split_axis=0,
                                  concat_axis=0, axis_index_groups=gg)
        elif plan.kind == "ppermute":
            recv = lax.ppermute(vals, plan.comm_axes, list(plan.perm))
        else:
            recv = vals
    with _part("unpack"):
        if q8:
            recv = jax.vmap(
                lambda s: q8_unpack(s, (R, C), dt, QUANT_TILE))(recv)
        rr = jnp.take(jnp.asarray(plan.recv_rows), dev, axis=0)
        rc = jnp.take(jnp.asarray(plan.recv_cols), dev, axis=0)
        out = jnp.zeros(plan.dst_local, recv.dtype)
        return out.at[rr[:, :, None], rc[:, None, :]].set(recv, mode="drop")


@partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _redistribute_direct_jit(A: DistMatrix, cdist: Dist, rdist: Dist,
                             calign: int = 0, ralign: int = 0,
                             wire=None) -> DistMatrix:
    plan = compile_plan(A.dist, (cdist, rdist), A.gshape,
                        (A.grid.height, A.grid.width),
                        (A.calign, A.ralign), (calign, ralign))
    out_meta = DistMatrix(None, A.gshape, cdist, rdist, calign, ralign,
                          A.grid)
    dt = A.dtype

    def f(a):
        x = a.local
        if wire == "bf16":
            with _part("pack"):
                x = x.astype(jnp.bfloat16)
        loc = _direct_exec(x, plan, wire, dt)
        with _part("unpack"):
            loc = loc.astype(dt)
        return DistMatrix(loc, A.gshape, cdist, rdist, calign, ralign,
                          A.grid)

    return shard_map(
        f, mesh=A.grid.mesh, in_specs=(A.spec,), out_specs=out_meta.spec,
        check_vma=False,
    )(A)


# ---------------------------------------------------------------------
# quantized wire precision (the ``comm_precision`` knob, ISSUE 8 --
# EQuARX direction, PAPERS.md 2506.17615): encode the payload narrow,
# run the SAME collective schedule on it, decode on the far side.  The
# codec lives in :mod:`.quantize`; this section is the engine routing.
# ---------------------------------------------------------------------

#: wire dtype names recorded on RedistRecord per resolved mode
_WIRE_DTYPES = {"bf16": "bfloat16", "int8": "int8"}

#: dists the fused int8 gather kernels understand (MD's slot permutation
#: and CIRC's eager bridge stay full precision)
_Q8_DISTS = frozenset({MC, MR, VC, VR, STAR})


def _wire_mode(A: DistMatrix, mode, q8_ok: bool):
    """Resolve a requested ``comm_precision`` to the wire mode actually
    run: ``None`` (bit-identical full precision), ``'bf16'``, or
    ``'int8'``.

    ``None`` is returned -- regardless of the request -- whenever
    quantization could not save a byte or would corrupt a non-codec
    payload: 1x1 grids (collectives elide), non-real-float dtypes, and
    replicated sources (pure-local filters).  ``'int8'`` requires a
    dedicated fused kernel (``q8_ok``: the gather-to-replicated family
    and ``panel_spread``); elsewhere the request degrades to the
    accuracy-SAFER ``'bf16'`` cast, which every pair supports."""
    check_comm_precision(mode)
    if mode is None:
        return None
    if A.grid.size == 1 or not quantizable(A.dtype):
        return None
    if A.dist == (STAR, STAR):
        return None                  # replicated source: pure local filter
    if mode == "int8":
        return "int8" if q8_ok else "bf16"
    return "bf16"


def _q8_gather_blocks(x, axes, tile: int):
    """all_gather whole per-device blocks at int8 wire precision: pack
    (payload + bitcast scales, one array), ONE collective, per-source
    decode.  Returns the ``(S, *x.shape)`` stack the interleave math of
    the full-precision kernels consumes unchanged."""
    with _part("pack"):
        packed = q8_pack(x, tile)
    with _part("wire"):
        gx = lax.all_gather(packed, axes, axis=0)
    with _part("unpack"):
        return jax.vmap(lambda b: q8_unpack(b, x.shape, x.dtype, tile))(gx)


def _gather_dim_q8(x, dim: int, d: Dist, extent: int, r: int, c: int,
                   tile: int):
    """Zero-aligned :func:`_gather_dim` with an int8 block-scaled wire."""
    S = dist_stride(d, r, c)
    if S == 1:
        with _part("unpack"):
            return lax.slice_in_dim(x, 0, extent, axis=dim)
    g = _q8_gather_blocks(x, gather_axes(d), tile)
    with _part("unpack"):
        return lax.slice_in_dim(_interleave(g, dim), 0, extent, axis=dim)


def _to_star_star_q8(A: DistMatrix, tile: int) -> DistMatrix:
    """:func:`to_star_star` at int8 wire precision -- same collective
    rounds (the fused 2-D gather when available, per-dim otherwise),
    ~4x fewer bytes on the wire."""
    g = A.grid
    r, c = g.height, g.width
    m, n = A.gshape
    x = A.local
    if A.dist in ((MC, MR), (MR, MC)) and r > 1 and c > 1:
        lr, lc = x.shape
        G = _q8_gather_blocks(x, ("mc", "mr"), tile)
        with _part("unpack"):
            full = lax.slice(_interleave_2d(G.reshape(r, c, lr, lc), A.dist),
                             (0, 0), (m, n))
        return DistMatrix(full, A.gshape, STAR, STAR, 0, 0, g)
    xg = _gather_dim_q8(x, 0, A.cdist, m, r, c, tile)
    xg = _gather_dim_q8(xg, 1, A.rdist, n, r, c, tile)
    return DistMatrix(xg, A.gshape, STAR, STAR, 0, 0, g)


@partial(jax.jit, static_argnums=(1,))
def _redistribute_q8_jit(A: DistMatrix, tile: int) -> DistMatrix:
    out_meta = DistMatrix(None, A.gshape, STAR, STAR, 0, 0, A.grid)

    def f(a):
        return _to_star_star_q8(a, tile)

    return shard_map(
        f, mesh=A.grid.mesh, in_specs=(A.spec,), out_specs=out_meta.spec,
        check_vma=False,
    )(A)


# ---------------------------------------------------------------------
# fused panel spread ([VC,STAR] -> the [MC,STAR]/[STAR,MR] operand pair)
# ---------------------------------------------------------------------

def _panel_spread_to_pair(A: DistMatrix, conj: bool, tile: int | None = None):
    """Inside shard_map: one (m, k) [VC,STAR] panel -> its [MC,STAR] spread
    AND its [STAR,MR] adjoint, in ONE collective round.

    A single all_gather over the flattened ('mr','mc') axis brings every
    device all ``p`` row blocks of the panel (at int8 wire precision when
    ``tile`` is given: packed, gathered, decoded per source); both outputs
    are then pure-local unpacks (plus the free local transpose for the
    adjoint).  The separate-call route costs three collective rounds: the
    [MC,STAR] partial gather, the VC->VR ppermute and the VR->MR partial
    gather of the adjoint chain.  The panels here are thin (k = nb << m),
    so they are latency-bound and one full-panel round beats three partial
    rounds despite moving ~m*k instead of ~m*k*(1/r + 1/c) per device --
    the collective-fusion trade of the array-redistribution literature
    (PAPERS.md 2112.01075).

    Panel row ``i = iLoc*p + v`` is row ``iLoc`` of block ``v = mc + r*mr``.
    [MC,STAR] keeps the rows ``i % r == mc``: the ``c`` blocks
    ``v = mc + r*t``, local row ``iLoc*c + t``.  The adjoint's [STAR,MR]
    keeps ``i % c == mr``: the ``r`` blocks ``v = mr + c*t``, local column
    ``iLoc*r + t``.  Each output interleaves only the blocks it keeps; the
    whole panel is never rebuilt and filtered (the same values at half
    the bytes moved, and no lane de-interleave; PERF.md 6, PR 29).
    """
    g = A.grid
    r, c = g.height, g.width
    m, k = A.gshape
    x = A.local
    if r * c == 1:
        blocks = x[None]
    elif tile is None:
        with _part("wire"):
            blocks = lax.all_gather(x, gather_axes(VC), axis=0)   # (p, l, k)
    else:
        blocks = _q8_gather_blocks(x, gather_axes(VC), tile)
    l = x.shape[0]

    def kept(d):
        # the p/S blocks v = rank + S*t that dist d keeps, interleaved
        S = dist_stride(d, r, c)
        mine = lax.dynamic_index_in_dim(blocks.reshape(r * c // S, S, l, k),
                                        rank_of(d, r, c), axis=1,
                                        keepdims=False)
        rows = _interleave(mine, 0)
        return lax.slice_in_dim(rows, 0, ix.max_local_length(m, S), axis=0)

    with _part("unpack"):
        mc = _zero_padding(kept(MC), (m, k), MC, STAR, 0, 0, g)
        adj = kept(MR).T
        if conj:
            adj = jnp.conj(adj)
        mr = _zero_padding(adj, (k, m), STAR, MR, 0, 0, g)
    return (DistMatrix(mc, (m, k), MC, STAR, 0, 0, g),
            DistMatrix(mr, (k, m), STAR, MR, 0, 0, g))


@partial(jax.jit, static_argnums=(1, 2))
def _panel_spread_jit(A: DistMatrix, conj: bool, wire=None):
    g = A.grid
    m, k = A.gshape
    dt = A.dtype
    mc_meta = DistMatrix(None, (m, k), MC, STAR, 0, 0, g)
    mr_meta = DistMatrix(None, (k, m), STAR, MR, 0, 0, g)

    def f(a):
        if wire == "int8":
            return _panel_spread_to_pair(a, conj, QUANT_TILE)
        if wire == "bf16":
            with _part("pack"):
                a = a.with_local(a.local.astype(jnp.bfloat16))
        mc, mr = _panel_spread_to_pair(a, conj)
        if wire == "bf16":
            with _part("unpack"):
                mc = mc.with_local(mc.local.astype(dt))
                mr = mr.with_local(mr.local.astype(dt))
        return mc, mr

    return shard_map(
        f, mesh=g.mesh, in_specs=(A.spec,),
        out_specs=(mc_meta.spec, mr_meta.spec), check_vma=False,
    )(A)


def panel_spread(A: DistMatrix, conj: bool = True, comm_precision=None):
    """``(A -> [MC,STAR],  op(A)^T -> [STAR,MR])`` for a zero-aligned
    [VC,STAR] panel, fused into a single collective round.

    The hot move of the Hermitian rank-k family: ``cholesky``'s trailing
    update and ``herk``/``her2k``'s per-panel chain all need exactly this
    operand pair for the ``LocalTrrk`` storage matmul.  ``conj=True``
    (default) produces the conjugate-transposed adjoint (``A^H``);
    ``conj=False`` the plain transpose (the ``syrk`` form).

    ``comm_precision`` (``None`` | ``'bf16'`` | ``'int8'``) selects the
    wire precision of the one collective (see :mod:`.quantize` and
    :func:`redistribute`): the panel is encoded narrow, gathered, and
    decoded back to its compute dtype on every device -- 2x/4x fewer
    bytes at the same round count.  ``None`` (default) is the
    bit-identical full-precision path."""
    if A.dist != (VC, STAR) or (A.calign, A.ralign) != (0, 0):
        raise ValueError(f"panel_spread needs a zero-aligned [VC,STAR] "
                         f"panel, got {A}")
    REDIST_COUNTS["panel_spread"] += 1
    wire = _wire_mode(A, comm_precision, q8_ok=True)
    with jax.named_scope("el.redist.panel_spread"):
        mc, mr = _panel_spread_jit(A, conj, wire)
    if _FAULT_INJECTOR is not None:
        lmc, lmr = _FAULT_INJECTOR.apply("panel_spread",
                                         (mc.local, mr.local))
        mc, mr = mc.with_local(lmc), mr.with_local(lmr)
    _trace_record("panel_spread", A.dist, ((MC, STAR), (STAR, MR)),
                  A.gshape, A.dtype, A.local, (mc.local, mr.local),
                  grid_shape=(A.grid.height, A.grid.width),
                  wire_dtype=_WIRE_DTYPES.get(wire))
    return mc, mr


# ---------------------------------------------------------------------
# batched storage-level row permutations (the COSTA-style one-shot plan)
# ---------------------------------------------------------------------

def _storage_row_of(i, S: int, lr: int):
    """Storage row of global row i for a stride-S zero-aligned column dim
    (stacked-storage layout: slot-major, then local offset)."""
    if S == 1:
        return i
    return (i % S) * lr + i // S


@_scoped("el.redist.row_permute")
def move_rows(A: DistMatrix, targets, sources, valid) -> DistMatrix:
    """Move global rows ``sources`` to positions ``targets`` in ONE
    storage-level gather/scatter pass, dropping entries where ``valid`` is
    False (sentinel padding).

    The batched-permutation fast path of the engine (COSTA direction,
    PAPERS.md 2106.06601): a panel's composed pivot permutation -- nb
    tournament winners plus the <= nb rows they displace, or partial
    pivoting's <= 2 nb moved rows -- is applied as a single collective
    plan on the stacked storage instead of a per-row swap chain.  The
    storage row map is a bijection between slots and virtual indices, so
    invalid slots are forced out of range rather than trusting the
    sentinel's arithmetic image.  No named collective is issued: the
    cross-device row motion lowers through GSPMD's partitioner, so the
    comm-plan analyzer sees the swap phase as zero explicit rounds
    (``REDIST_COUNTS['row_permute']`` still counts the entry calls).

    Counted at trace time in ``row_permute{kind="move"}``, with the rows
    asked to move in ``row_permute_rows`` and the worst-case bytes a device
    receives (every moved row crossing chips) in ``row_permute_wire_bytes``:
    what a panel step asks of the wire, whatever the partitioner makes of
    it."""
    REDIST_COUNTS["row_permute"] += 1
    S, lr = A.col_stride, A.local_rows
    m = A.gshape[0]
    sidx = _storage_row_of(jnp.clip(targets, 0, m - 1), S, lr)
    sidx = jnp.where(valid, sidx, S * lr)          # OOB => scatter drops
    gsrc = _storage_row_of(jnp.clip(sources, 0, m - 1), S, lr)
    stor = A.local
    rows = jnp.take(stor, gsrc, axis=0)
    out = A.with_local(stor.at[sidx].set(rows, mode="drop"))
    # wire traffic: <= moved rows x local row width, worst case all
    # cross-chip
    k = int(targets.shape[0])
    _record_row_permute("move", A, out, k,
                        k * stor.shape[1] * jnp.dtype(A.dtype).itemsize)
    return out


@_scoped("el.redist.row_permute")
def permute_rows_storage(A: DistMatrix, perm, inverse: bool = False
                         ) -> DistMatrix:
    """``B[i] = A[perm[i]]`` as ONE storage-level gather for a zero-aligned
    row-cyclic matrix (full-permutation sibling of :func:`move_rows`).

    Replaces the historical [STAR,VR] round trip (two collective rounds:
    demote + promote) with a single storage gather whose cross-device
    motion GSPMD plans directly -- the engine-level fast path behind
    ``lapack.lu.permute_rows``.  Counted as ``row_permute{kind="full"}``
    (see :func:`move_rows`)."""
    if (A.calign, A.ralign) != (0, 0):
        raise ValueError(f"permute_rows_storage needs zero alignments, got {A}")
    REDIST_COUNTS["row_permute"] += 1
    p = jnp.argsort(perm) if inverse else perm
    m = A.gshape[0]
    S, lr = A.col_stride, A.local_rows
    if S == 1:
        res = A.with_local(jnp.take(A.local, p, axis=0))
    else:
        sr = jnp.arange(S * lr)
        gi = (sr % lr) * S + sr // lr              # global row of storage slot
        src = _storage_row_of(p[jnp.clip(gi, 0, m - 1)], S, lr)
        out = jnp.take(A.local, src, axis=0)
        out = jnp.where((gi < m)[:, None], out, 0)  # keep padding zeroed
        res = A.with_local(out)
    # wire traffic: worst case the whole local block crosses chips
    _record_row_permute("full", A, res, m,
                        int(A.local.size) * jnp.dtype(A.dtype).itemsize)
    return res


def _record_row_permute(kind: str, A: DistMatrix, out: DistMatrix,
                        rows: int, wire_bytes: int) -> None:
    """One storage-level row permutation, for the counters and the
    observers: ``row_permute{kind}`` entries, the rows they move and the
    worst-case bytes a device receives; and the observer seam (ISSUE 12):
    the obs tracer must see this entry's wire traffic even though GSPMD
    plans the motion -- ``observers_only`` keeps it OUT of the comm-plan
    golden aggregation, which pins explicit rounds."""
    _metrics.inc("row_permute", kind=kind)
    _metrics.inc("row_permute_rows", rows, kind=kind)
    _metrics.inc("row_permute_wire_bytes", wire_bytes, kind=kind)
    _trace_record("row_permute", A.dist, A.dist, (rows, A.gshape[1]),
                  A.dtype, A.local, (out.local,),
                  grid_shape=(A.grid.height, A.grid.width),
                  path="storage", rounds=0, wire_bytes=wire_bytes,
                  observers_only=True)


# ---------------------------------------------------------------------
# transpose-dist ([U,V] -> [V,U] with local transpose; free)
# ---------------------------------------------------------------------

def transpose_dist(A: DistMatrix, conj: bool = False) -> DistMatrix:
    """A^T tagged [rdist, cdist] -- Elemental's ``copy::TransposeDist``."""
    loc = A.local.T
    if conj:
        loc = jnp.conj(loc)
    m, n = A.gshape
    return DistMatrix(loc, (n, m), A.rdist, A.cdist, A.ralign, A.calign, A.grid)


# ---------------------------------------------------------------------
# Contract / SumScatter (partial products -> distributed sum)
# ---------------------------------------------------------------------

def contract(A: DistMatrix, cdist: Dist, rdist: Dist) -> DistMatrix:
    """Sum partial contributions held per-device and land on [cdist,rdist].

    The reference's ``Contract``/``AxpyContract`` (``src/blas_like/level1/
    Contract.cpp``): e.g. partial [MC,STAR] -> [MC,MR] is a ReduceScatter
    over the MR comm; here ``lax.psum_scatter`` after a local residue-block
    rearrangement (cyclic target layout).  Zero alignments.
    """
    g = A.grid
    r, c = g.height, g.width
    m, n = A.gshape
    src = (A.cdist, A.rdist)
    dst = (cdist, rdist)
    if src == (MC, STAR) and dst == (MC, MR):
        loc = _scatter_sum_dim(A.local, 1, "mr", c, ix.max_local_length(n, c))
        return DistMatrix(loc, A.gshape, MC, MR, A.calign, 0, g)
    if src == (STAR, MR) and dst == (MC, MR):
        loc = _scatter_sum_dim(A.local, 0, "mc", r, ix.max_local_length(m, r))
        return DistMatrix(loc, A.gshape, MC, MR, 0, A.ralign, g)
    if src == (MR, STAR) and dst == (MR, MC):
        loc = _scatter_sum_dim(A.local, 1, "mc", r, ix.max_local_length(n, r))
        return DistMatrix(loc, A.gshape, MR, MC, A.calign, 0, g)
    if src == (STAR, MC) and dst == (MR, MC):
        loc = _scatter_sum_dim(A.local, 0, "mr", c, ix.max_local_length(m, c))
        return DistMatrix(loc, A.gshape, MR, MC, 0, A.ralign, g)
    if src == (STAR, STAR) and dst == (MC, MR):
        loc = _scatter_sum_dim(A.local, 0, "mc", r, ix.max_local_length(m, r))
        loc = _scatter_sum_dim(loc, 1, "mr", c, ix.max_local_length(n, c))
        return DistMatrix(loc, A.gshape, MC, MR, 0, 0, g)
    if src == (STAR, STAR) and dst == (STAR, STAR):
        # partial replicated -> full sum everywhere
        with _part("wire"):
            loc = lax.psum(lax.psum(A.local, "mc"), "mr")
        return DistMatrix(loc, A.gshape, STAR, STAR, 0, 0, g)
    if src == (STAR, STAR) and dst == (VC, STAR):
        ss = contract(A, STAR, STAR)
        return to_dist(ss, VC, STAR)
    raise NotImplementedError(f"contract {src} -> {dst}")


def _scatter_sum_dim(x, dim: int, axis_name: str, S: int, l_out: int):
    """psum_scatter a replicated-partial dimension onto its cyclic owners."""
    if S == 1:
        with _part("unpack"):
            return _pad_dim(x, dim, l_out)
    with _part("pack"):
        x = _pad_dim(x, dim, S * l_out)
        shape = list(x.shape)
        shape[dim : dim + 1] = [l_out, S]
        x = x.reshape(shape)                   # (..., l_out, S, ...)
        x = jnp.moveaxis(x, dim + 1, dim)      # (..., S, l_out, ...) residue-major
        shape2 = list(x.shape)
        shape2[dim : dim + 2] = [S * l_out]
        x = x.reshape(shape2)
    with _part("wire"):
        return lax.psum_scatter(x, axis_name, scatter_dimension=dim,
                                tiled=True)


# ---------------------------------------------------------------------
# public jit-able wrapper
# ---------------------------------------------------------------------

def redistribute(A: DistMatrix, cdist: Dist, rdist: Dist,
                 calign: int = 0, ralign: int = 0,
                 comm_precision=None, path=None) -> DistMatrix:
    """B[cdist,rdist] = A, as a standalone (jit-able) op on storage-form
    DistMatrix.  ``Copy(A, B)`` / ``operator=`` of the reference.

    jit-cached on (static metadata, dst dists, aligns): eager callers (tests,
    blocked loops run outside an enclosing jit) hit the compile cache instead
    of re-tracing a fresh ``shard_map`` closure per call.

    ``comm_precision`` (``None`` | ``'bf16'`` | ``'int8'``) opts this
    entry into a narrow wire encoding (:mod:`.quantize`): the payload is
    encoded inside the jitted shard_map, the collectives move the narrow
    dtype (the comm-plan analyzer sees the true wire bytes), and the
    result decodes back to the source dtype.  ``'bf16'`` applies to every
    pair; ``'int8'`` (block-scaled, packed scales, round-identical) has a
    fused kernel for the zero-aligned gather-to-[STAR,STAR] family and
    degrades to ``'bf16'`` elsewhere.  ``None`` (default) is the
    bit-identical full-precision path; the knob is a no-op on 1x1 grids,
    non-real-float payloads, and replicated sources (pure-local filters).

    ``path`` (see :data:`REDIST_PATHS`, ISSUE 12/13) selects the route:
    ``None``/``'chain'`` run the factored multi-hop dispatch (bit-identical
    to the historical engine); ``'direct'`` executes the ONE-SHOT compiled
    plan (:mod:`.plan` -- a single all_to_all/ppermute with static ragged
    gather/scatter index maps), which since phase 2 covers every legal
    pair at every legal alignment (MD included; CIRC endpoints compile to
    a costed bridge executed on the eager root path), falling back to the
    chain only for no-ops; ``'auto'`` compiles the plan and takes it only
    where the alpha-beta cost -- measured ``redist_constants/v1`` when
    ``perf.redist_bench --record`` has written them for this (grid,
    backend), the static ring model otherwise -- says it beats the chain
    (ties go to the chain).  Fallbacks increment the ``redist_fallbacks``
    obs counter and stamp ``RedistRecord.fallback_reason``.  On the
    direct route an ``'int8'`` ``comm_precision`` block-scale-packs every
    plan slot, so the narrow payload rides ANY pair's single collective
    -- not just the gather-to-[STAR,STAR] family.

    CIRC conversions (root-only storage) route their collective leg
    through the SAME compiled ``_redistribute_jit`` as every other pair
    (copy::Gather fuses to one gather chain to ``[STAR,STAR]``;
    copy::Scatter is a zero-collective local filter); only the root-edge
    ``device_put`` itself stays outside the shard_map."""
    _check_pair(cdist, rdist)
    if path not in REDIST_PATHS:
        raise ValueError(f"path must be one of {REDIST_PATHS}, got {path!r}")
    with jax.named_scope(f"el.redist.{A.cdist.name}_{A.rdist.name}.to."
                         f"{cdist.name}_{rdist.name}"):
        return _redistribute(A, cdist, rdist, calign, ralign,
                             comm_precision, path)


def _redistribute(A: DistMatrix, cdist: Dist, rdist: Dist, calign: int,
                  ralign: int, comm_precision, path) -> DistMatrix:
    """:func:`redistribute` after its argument checks, inside its
    ``el.redist.<SRC>.to.<DST>`` scope: the route, the collectives and the
    local pack / unpack beside them."""
    REDIST_COUNTS[(A.dist, (cdist, rdist))] += 1
    grid_shape = (A.grid.height, A.grid.width)
    circ = cdist is CIRC or A.cdist is CIRC
    noop = A.dist == (cdist, rdist) \
        and (A.calign, A.ralign) == (calign, ralign)
    plan = None
    fallback_reason = ""
    if path in ("direct", "auto"):
        if noop:
            fallback_reason = "noop"
        else:
            plan = direct_plan_for(A, cdist, rdist, calign, ralign)
            if plan is None:
                fallback_reason = "no_plan"
            elif path == "auto" and plan.kind != "bridge" and \
                    not _direct_wins(plan, A.gshape,
                                     jnp.dtype(A.dtype).itemsize):
                plan, fallback_reason = None, "arbitration"
    if fallback_reason:
        _metrics.inc("redist_fallbacks", reason=fallback_reason)
    if plan is not None and not circ:
        wire = None if plan.kind == "local" \
            else _wire_mode(A, comm_precision, q8_ok=True)
        out = _redistribute_direct_jit(A, cdist, rdist, calign, ralign, wire)
        if _FAULT_INJECTOR is not None:
            out = out.with_local(
                _FAULT_INJECTOR.apply("redistribute", (out.local,))[0])
        wire_sz = {"bf16": 2, "int8": 1}.get(wire, jnp.dtype(A.dtype).itemsize)
        _trace_record("redistribute", A.dist, (cdist, rdist), A.gshape,
                      A.dtype, A.local, (out.local,), grid_shape=grid_shape,
                      wire_dtype=_WIRE_DTYPES.get(wire), path="direct",
                      rounds=plan.rounds, wire_bytes=plan.wire_bytes(wire_sz))
        return out
    if circ:
        check_comm_precision(comm_precision)
        wire = None
        out = _redistribute_circ(A, cdist, rdist, calign, ralign)
    else:
        q8_ok = ((cdist, rdist) == (STAR, STAR)
                 and (calign, ralign) == (0, 0) and _zero_aligned(A)
                 and set(A.dist) <= _Q8_DISTS)
        wire = None if noop else _wire_mode(A, comm_precision, q8_ok)
        if wire == "int8":
            out = _redistribute_q8_jit(A, QUANT_TILE)
        else:
            out = _redistribute_jit(A, cdist, rdist, calign, ralign, wire)
    if _FAULT_INJECTOR is not None:
        out = out.with_local(
            _FAULT_INJECTOR.apply("redistribute", (out.local,))[0])
    if plan is not None:
        # CIRC bridge under 'direct'/'auto': executed by the eager root
        # path above, recorded as the direct route with the plan's
        # honest full-matrix cost (arbitration does not apply -- the
        # chain route IS the same eager bridge)
        _trace_record("redistribute", A.dist, (cdist, rdist), A.gshape,
                      A.dtype, A.local, (out.local,), grid_shape=grid_shape,
                      wire_dtype=_WIRE_DTYPES.get(wire), path="direct",
                      rounds=plan.rounds,
                      wire_bytes=plan.wire_bytes(jnp.dtype(A.dtype).itemsize))
        return out
    rounds = wire_bytes = -1
    if not circ and not noop and _zero_aligned(A) and (calign, ralign) == (0, 0):
        wire_sz = {"bf16": 2, "int8": 1}.get(wire, jnp.dtype(A.dtype).itemsize)
        rounds, wire_bytes = chain_cost(A.dist, (cdist, rdist), A.gshape,
                                        grid_shape, wire_sz)
    _trace_record("redistribute", A.dist, (cdist, rdist), A.gshape,
                  A.dtype, A.local, (out.local,),
                  grid_shape=grid_shape,
                  wire_dtype=_WIRE_DTYPES.get(wire), path="chain",
                  rounds=rounds, wire_bytes=wire_bytes,
                  fallback_reason=fallback_reason)
    return out


def _redistribute_circ(A: DistMatrix, cdist: Dist, rdist: Dist,
                       calign: int, ralign: int) -> DistMatrix:
    """CIRC endpoints via the JITTED shard_map path (ISSUE 14 satellite).

    PR 9-13 ran these through the eager global bridges (``to_global`` /
    ``from_global``: per-dimension index-map gathers executed op-by-op,
    whose implicit cross-device resharding paid a host sync at this
    edge -- the ROADMAP's ``'bridge'`` leftover).  Both directions now
    route every collective through the SAME compiled ``_redistribute_jit``
    as the non-CIRC pairs -- ``[STAR,STAR]`` storage IS the global array
    (identity index maps), so only a root ``device_put`` remains at the
    edge:

      * dst CIRC: ONE fused gather chain to ``[STAR,STAR]``, then a
        comm-free root-local ``device_put`` (``copy::Gather``);
      * src CIRC: root-broadcast ``device_put`` (``copy::Scatter``),
        then a ZERO-collective jitted local filter to the target pair.
    """
    import jax.sharding as jsh
    g = A.grid
    if A.cdist is CIRC and cdist is CIRC:
        return A
    if cdist is CIRC:
        star = _redistribute_jit(A, STAR, STAR, 0, 0, None)
        arr = jax.device_put(
            star.local, jsh.SingleDeviceSharding(g.mesh.devices.flat[0]))
        return DistMatrix(arr, A.gshape, CIRC, CIRC, 0, 0, g)
    # CIRC source: broadcast the root array, wrap it as [STAR,STAR]
    # (identity storage form), then filter locally inside the jitted path
    arr = jax.device_put(A.local, g.sharding(jax.sharding.PartitionSpec()))
    star = DistMatrix(arr, A.gshape, STAR, STAR, 0, 0, g)
    return _redistribute_jit(star, cdist, rdist, calign, ralign, None)


@partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _redistribute_jit(A: DistMatrix, cdist: Dist, rdist: Dist,
                      calign: int, ralign: int, wire=None) -> DistMatrix:
    out_meta = DistMatrix(None, A.gshape, cdist, rdist, calign, ralign, A.grid)
    dt = A.dtype

    def f(a):
        # bf16 wire: the cast sits INSIDE the traced program, so every
        # collective of the chain moves bfloat16 (half the bytes) and the
        # jaxpr-level analyzer reads the true payload dtype off the
        # collective operand
        if wire == "bf16":
            with _part("pack"):
                a = a.with_local(a.local.astype(jnp.bfloat16))
        out = to_dist(a, cdist, rdist, calign, ralign)
        if wire == "bf16":
            with _part("unpack"):
                out = out.with_local(out.local.astype(dt))
        return out

    return shard_map(
        f, mesh=A.grid.mesh, in_specs=(A.spec,), out_specs=out_meta.spec,
        check_vma=False,
    )(A)
