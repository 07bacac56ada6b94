"""Static memory-plan analyzer (ISSUE 18): jaxpr liveness -> peak bytes.

The memory twin of the comm-plan subsystem.  Where :mod:`.jaxpr_walk`
extracts every collective a traced driver issues, this module walks the
SAME closed jaxpr and computes what the program keeps *resident*:

* **per-device peak live bytes** -- a last-use liveness walk over every
  equation, recursing into ``jit`` calls, ``shard_map`` bodies and
  ``scan``/``while``/``cond`` sub-jaxprs exactly like the collective
  walker.  Inside ``shard_map`` the avals are already per-device and are
  counted verbatim; outside, stacked-storage arrays are sharded over the
  mesh (``DistMatrix.spec`` tiles the storage array), so top-level avals
  count at ``ceil(bytes / p)``.  The known blind spot of that model --
  replicated residents whose storage aval LOOKS sharded -- is closed by
  the census below, not hand-waved;
* **a timeline of high-water marks** -- every time the live total sets a
  new peak, the (nesting path, primitive, live bytes) triple is recorded,
  so a regression names the scope that grew instead of a bare number;
* **a census of replicated materializations** -- every engine
  redistribution whose destination form keeps more than one copy of the
  operand per ``p`` devices ( ``[STAR,STAR]`` gathers, the ``[MC,STAR]``
  / ``[STAR,MR]`` panel forms, root-only ``[CIRC,CIRC]``), with the
  per-device bytes it costs OVER the evenly-sharded model.  The headline
  ``peak_bytes`` = walk peak + the largest single replicated extra (at
  least one replicated form is live at its own high-water mark; summing
  all of them would double-count sequential panel gathers that free
  between steps).

``while`` bodies have no static trip count, so allocations inside them
are EXCLUDED from the pinned ``peak_bytes`` and accumulated separately as
``nonstatic_peak_bytes`` -- surfaced by lint (EL006 folds it into the
budget check), never silently folded into a golden number.

The ``memory_plan/v1`` JSON document is pinned per registered driver
variant under ``tests/golden/memory_plans/`` by the same CLI pattern as
the comm plans: ``python -m perf.comm_audit mem|mem-diff
--update-golden``.

This module also owns the static VMEM cross-check behind lint EL007:
:func:`check_panel_vmem` recomputes, per fused-kernel dispatch site in
``kernels/``, BOTH the bytes the :meth:`PanelPlan.use_pallas` gate prices
(``copies`` tile-padded residents) and the bytes the kernel actually
allocates (its real ``pallas_call`` out_shapes + in-kernel carries,
including the square LANE-padding the Cholesky/larft kernels apply that
the gate's (8, 128) tile padding understates) -- proving the 16 MiB gate
conservative instead of trusting it.
"""
from __future__ import annotations

import dataclasses
import json

import numpy as np

from jax.extend import core as jcore

from ..core.dist import stride as dist_stride
from ..kernels.common import (LANE, PANEL_VMEM_BUDGET, PANEL_VMEM_LIMIT,
                              SUBLANE, round_up)
from .jaxpr_walk import _scope_label, _sub_jaxprs

MEM_SCHEMA = "memory_plan/v1"

#: high-water marks kept in the timeline (peaks are monotone, so these
#: are the LAST -- i.e. highest -- marks of the walk)
TIMELINE_CAP = 8


# ---------------------------------------------------------------------
# liveness walk
# ---------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class HighWater:
    """One new-peak event of the liveness walk."""
    live_bytes: int
    path: tuple                  # nesting scopes from the root jaxpr
    prim: str                    # primitive whose output set the peak

    def to_doc(self) -> dict:
        return {"live_bytes": self.live_bytes, "path": "/".join(self.path),
                "prim": self.prim}


@dataclasses.dataclass
class WalkStats:
    """The liveness walk's result for one closed jaxpr."""
    peak_bytes: int              # per-device peak live (static scopes only)
    peak_path: tuple
    peak_prim: str
    args_bytes: int              # per-device input + trace-const residency
    outs_bytes: int              # per-device output residency
    timeline: list               # list[HighWater], last TIMELINE_CAP peaks
    nonstatic_peak_bytes: int    # high water of while-body allocations

    @property
    def static(self) -> bool:
        return self.nonstatic_peak_bytes == 0


class _State:
    __slots__ = ("live", "peak", "peak_path", "peak_prim", "timeline",
                 "ns_live", "ns_peak")

    def __init__(self):
        self.live = 0
        self.peak = 0
        self.peak_path = ()
        self.peak_prim = ""
        self.timeline = []
        self.ns_live = 0
        self.ns_peak = 0

    def alloc(self, nbytes: int, path, prim: str, static: bool) -> None:
        if nbytes <= 0:
            return
        if not static:
            self.ns_live += nbytes
            if self.ns_live > self.ns_peak:
                self.ns_peak = self.ns_live
            return
        self.live += nbytes
        if self.live > self.peak:
            self.peak = self.live
            self.peak_path = path
            self.peak_prim = prim
            self.timeline.append(HighWater(self.live, path, prim))
            if len(self.timeline) > TIMELINE_CAP:
                self.timeline.pop(0)

    def free(self, nbytes: int, static: bool) -> None:
        if nbytes <= 0:
            return
        if static:
            self.live -= nbytes
        else:
            self.ns_live -= nbytes


def _aval_bytes(aval, div: int) -> int:
    """Per-device bytes of one aval: total bytes / ``div``, ceil'd.

    ``div`` is the device count for top-level (storage-sharded) scopes
    and 1 inside ``shard_map`` bodies, where avals are already local."""
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    try:
        n = 1
        for s in shape:
            n *= int(s)
        nbytes = n * np.dtype(dtype).itemsize
    except (TypeError, ValueError):      # symbolic dims / exotic dtypes
        return 0
    return -(-nbytes // max(int(div), 1))


def _walk_scope(jaxpr, div: int, path: tuple, static: bool,
                state: _State) -> None:
    """Liveness walk of one scope.

    Protocol: the scope's invars/constvars are the CALLER's residents
    (aliased, never double counted here); everything allocated inside --
    including the scope's outvars -- is freed on exit, and the caller
    allocates its own eqn outvars afterward.  The transient "freed then
    re-allocated" boundary never lowers the recorded peak because the
    peak was taken while the scope's outputs were live inside it."""
    # optimization_barrier is an identity the compiler keeps in place: its
    # results ARE its operands' buffers, so a use of a result is a use of
    # the operand and the barrier itself allocates nothing
    alias: dict = {}
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "optimization_barrier":
            for out, src in zip(eqn.outvars, eqn.invars):
                alias[out] = alias.get(src, src)
    last: dict = {}
    for idx, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if isinstance(v, jcore.Var):
                last[alias.get(v, v)] = idx
    end = len(jaxpr.eqns)
    for v in jaxpr.outvars:
        if isinstance(v, jcore.Var):
            last[alias.get(v, v)] = end
    inner: dict = {}                     # var -> (bytes, static)
    for idx, eqn in enumerate(jaxpr.eqns):
        prim = eqn.primitive.name
        if prim == "optimization_barrier":
            continue
        sub_div = 1 if prim == "shard_map" else div
        sub_static = static and prim != "while"
        label = _scope_label(eqn)
        if prim == "cond":
            # branches walked from the same entry residency; free-on-exit
            # makes the recorded peak the max over branches
            for i, branch in enumerate(eqn.params.get("branches", ())):
                for sub in _sub_jaxprs(branch):
                    _walk_scope(sub, sub_div, path + (f"cond[{i}]",),
                                sub_static, state)
        else:
            for val in eqn.params.values():
                for sub in _sub_jaxprs(val):
                    _walk_scope(sub, sub_div, path + (label,),
                                sub_static, state)
        for v in eqn.outvars:
            b = _aval_bytes(getattr(v, "aval", None), div)
            state.alloc(b, path, prim, static)
            if isinstance(v, jcore.Var) and last.get(v, -1) > idx:
                inner[v] = (b, static)
            else:                        # DropVar / immediately dead
                state.free(b, static)
        for v in set(alias.get(x, x) for x in eqn.invars
                     if isinstance(x, jcore.Var)):
            if last.get(v) == idx and v in inner:
                b, st = inner.pop(v)
                state.free(b, st)
    for b, st in inner.values():
        state.free(b, st)


def analyze_jaxpr(closed_jaxpr, grid_size: int = 1) -> WalkStats:
    """Liveness-walk a closed jaxpr; return per-device :class:`WalkStats`.

    ``grid_size`` is the device count ``p`` of the traced mesh: top-level
    storage avals count at ``ceil(bytes / p)`` (see module docstring for
    the replicated-form caveat and its census-based correction)."""
    jaxpr = closed_jaxpr.jaxpr \
        if isinstance(closed_jaxpr, jcore.ClosedJaxpr) else closed_jaxpr
    consts = getattr(closed_jaxpr, "consts", ())
    div = max(int(grid_size), 1)
    state = _State()
    args = 0
    for v in jaxpr.invars:
        args += _aval_bytes(getattr(v, "aval", None), div)
    for c in consts:
        nb = getattr(c, "nbytes", None)
        if nb is None:
            try:
                nb = np.asarray(c).nbytes
            except (TypeError, ValueError):
                nb = 0
        args += -(-int(nb) // div)
    outs = sum(_aval_bytes(getattr(v, "aval", None), div)
               for v in jaxpr.outvars if isinstance(v, jcore.Var))
    # inputs + trace constants are resident for the whole program
    state.alloc(args, ("<args>",), "input", True)
    _walk_scope(jaxpr, div, (), True, state)
    return WalkStats(peak_bytes=state.peak, peak_path=state.peak_path,
                     peak_prim=state.peak_prim, args_bytes=args,
                     outs_bytes=outs, timeline=list(state.timeline),
                     nonstatic_peak_bytes=state.ns_peak)


# ---------------------------------------------------------------------
# replicated-materialization census (redist-log level)
# ---------------------------------------------------------------------

def _replication(dst, grid_shape) -> int:
    """Copies of the operand per ``p`` devices in the ``dst`` form.

    1 for evenly sharded pairs ([MC,MR], [VC,STAR], ...); ``c`` for
    [MC,STAR]; ``p`` for [STAR,STAR].  [CIRC,CIRC] prices like
    [STAR,STAR]: the root holds the FULL operand, and peak accounting
    cares about the worst device."""
    r, c = int(grid_shape[0]), int(grid_shape[1])
    p = max(r * c, 1)
    cover = min(dist_stride(dst[0], r, c) * dist_stride(dst[1], r, c), p)
    return max(1, p // max(cover, 1))


def replication_census(redist_log, grid_shape) -> dict:
    """Aggregate the engine's redistribution log into the replicated
    section of a ``memory_plan/v1`` document.

    ``extra_bytes`` of one materialization = the per-device bytes its
    destination form keeps ABOVE the evenly-sharded model the liveness
    walk prices (``total * (repl - 1) / p``)."""
    r, c = int(grid_shape[0]), int(grid_shape[1])
    p = max(r * c, 1)
    agg: dict = {}
    star_star = 0
    max_extra = 0
    sum_extra = 0
    for rec in redist_log:
        gs = tuple(rec.grid_shape or (r, c))
        # "panel_spread" produces BOTH panel forms ([MC,*] and [*,MR])
        # from one entry; a plain "redistribute" targets one pair
        dst_pairs = rec.dst if rec.kind == "panel_spread" else (rec.dst,)
        try:
            z = np.dtype(rec.dtype).itemsize
        except TypeError:
            z = 4
        total = int(rec.gshape[0]) * int(rec.gshape[1]) * z
        rec_extra = 0
        for dst in dst_pairs:
            repl = _replication(dst, gs)
            if repl <= 1:
                continue
            names = tuple(d.value for d in dst)
            extra = total * (repl - 1) // max(gs[0] * gs[1], 1)
            if names == ("STAR", "STAR"):
                star_star += 1
            rec_extra += extra
            sum_extra += extra
            key = (f"[{names[0]},{names[1]}]",
                   tuple(int(x) for x in rec.gshape), str(rec.dtype))
            site = agg.setdefault(key, {"count": 0, "extra_bytes": 0})
            site["count"] += 1
            site["extra_bytes"] += extra
        # one entry's forms coexist, so its extras sum for the headline
        max_extra = max(max_extra, rec_extra)
    sites = [{"dst": dst, "gshape": list(gshape), "dtype": dt,
              "count": s["count"], "extra_bytes": s["extra_bytes"]}
             for (dst, gshape, dt), s in sorted(agg.items(),
                                                key=lambda kv: repr(kv[0]))]
    return {"count": sum(s["count"] for s in sites),
            "star_star": star_star, "max_extra_bytes": max_extra,
            "sum_extra_bytes": sum_extra, "sites": sites}


# ---------------------------------------------------------------------
# the memory plan document
# ---------------------------------------------------------------------

@dataclasses.dataclass
class MemoryPlan:
    """The extracted memory profile of one traced driver call."""
    driver: str
    grid: tuple                  # (r, c)
    meta: dict                   # n, nb, dtype, driver knobs (comm-plan meta)
    stats: WalkStats
    replicated: dict             # replication_census() output

    @property
    def peak_bytes(self) -> int:
        """The budgetable headline: walk peak + the largest replicated
        extra (see module docstring for why max, not sum)."""
        return self.stats.peak_bytes + int(
            self.replicated.get("max_extra_bytes", 0))

    @property
    def static(self) -> bool:
        return self.stats.static

    def to_doc(self) -> dict:
        doc = {"schema": MEM_SCHEMA, "driver": self.driver,
               "grid": list(self.grid)}
        doc.update(self.meta)
        doc["static"] = self.static
        doc["peak_bytes"] = self.peak_bytes
        doc["walk_peak_bytes"] = self.stats.peak_bytes
        doc["peak_path"] = "/".join(self.stats.peak_path)
        doc["peak_prim"] = self.stats.peak_prim
        doc["args_bytes"] = self.stats.args_bytes
        doc["outs_bytes"] = self.stats.outs_bytes
        doc["nonstatic_peak_bytes"] = self.stats.nonstatic_peak_bytes
        doc["replicated"] = dict(self.replicated)
        doc["timeline"] = [hw.to_doc() for hw in self.stats.timeline]
        return doc

    def to_json(self, indent: int = 1) -> str:
        return json.dumps(self.to_doc(), indent=indent, sort_keys=False)


def memory_plan(driver: str, grid, meta: dict, closed_jaxpr,
                redist_log=()) -> MemoryPlan:
    """Assemble a :class:`MemoryPlan` from one abstract driver trace."""
    grid = tuple(int(g) for g in grid)
    p = max(grid[0] * grid[1], 1)
    stats = analyze_jaxpr(closed_jaxpr, grid_size=p)
    census = replication_census(redist_log, grid)
    return MemoryPlan(driver=driver, grid=grid, meta=dict(meta),
                      stats=stats, replicated=census)


def trace_memory(name: str, grid, n=None, nb=None, dtype=None):
    """Trace a registered driver and return ``(MemoryPlan, closed_jaxpr,
    redist_log)`` -- the memory twin of :func:`..drivers.trace_driver`."""
    import jax.numpy as jnp
    from .drivers import DEFAULT_N, DEFAULT_NB, trace_driver
    kwargs = {"n": DEFAULT_N if n is None else n,
              "nb": DEFAULT_NB if nb is None else nb}
    if dtype is not None:
        kwargs["dtype"] = dtype
    else:
        kwargs["dtype"] = jnp.float32
    plan, closed, log = trace_driver(name, grid, **kwargs)
    mplan = memory_plan(name, (grid.height, grid.width), plan.meta,
                        closed, log)
    return mplan, closed, log


def golden_mem_doc(mplan: MemoryPlan) -> dict:
    """The snapshot form (currently the full document -- memory plans
    carry no per-event audit detail the way comm plans do)."""
    return mplan.to_doc()


def diff_mem_docs(golden: dict, current: dict) -> list:
    """Human-readable mismatch lines between two memory_plan/v1 docs."""
    lines: list = []
    scalar_keys = ("schema", "driver", "grid", "n", "nb", "dtype", "static",
                   "peak_bytes", "walk_peak_bytes", "peak_path", "peak_prim",
                   "args_bytes", "outs_bytes", "nonstatic_peak_bytes")
    for key in scalar_keys:
        if golden.get(key) != current.get(key):
            lines.append(f"{key}: golden={golden.get(key)!r} "
                         f"current={current.get(key)!r}")
    gr = golden.get("replicated", {})
    cr = current.get("replicated", {})
    for key in ("count", "star_star", "max_extra_bytes", "sum_extra_bytes"):
        if gr.get(key) != cr.get(key):
            lines.append(f"replicated[{key}]: golden={gr.get(key)} "
                         f"current={cr.get(key)}")

    def _rows(doc_rep):
        return set(json.dumps(s, sort_keys=True, default=str)
                   for s in doc_rep.get("sites", []))

    gs, cs = _rows(gr), _rows(cr)
    for row in sorted(gs - cs):
        lines.append(f"replicated site missing vs golden: {row}")
    for row in sorted(cs - gs):
        lines.append(f"replicated site not in golden: {row}")
    gt = golden.get("timeline", [])
    ct = current.get("timeline", [])
    if gt != ct:
        lines.append(f"timeline: golden={len(gt)} mark(s) "
                     f"{json.dumps(gt[-1] if gt else None, default=str)} "
                     f"current={len(ct)} mark(s) "
                     f"{json.dumps(ct[-1] if ct else None, default=str)}")
    return lines


# ---------------------------------------------------------------------
# static VMEM cross-check (lint EL007 support)
# ---------------------------------------------------------------------

#: resident-copy count each driver dispatch site passes to
#: :meth:`PanelPlan.use_pallas` -- pinned against the actual call sites
#: (lapack/lu.py, lapack/cholesky.py, lapack/qr.py) by tests/analysis.
PANEL_GATE_COPIES = {"lu": 3, "cholesky": 4, "qr": 4}


@dataclasses.dataclass(frozen=True)
class PanelVmemCheck:
    """One gate-vs-kernel cross-check of a fused panel dispatch."""
    op: str
    shape: tuple
    dtype: str
    gate_bytes: int              # what use_pallas prices (copies x tiles)
    kernel_bytes: int            # what the compiled kernel allocates
    budget: int                  # the gate's budget
    limit: int                   # the scoped-VMEM limit it is compiled with
    admitted: bool               # gate_bytes <= budget (use_pallas yes)
    fits: bool                   # kernel_bytes <= limit

    @property
    def overflow(self) -> bool:
        """True when the gate would admit a kernel that overflows."""
        return self.admitted and not self.fits

    def to_doc(self) -> dict:
        return {"op": self.op, "shape": list(self.shape),
                "dtype": self.dtype, "gate_bytes": self.gate_bytes,
                "kernel_bytes": self.kernel_bytes, "budget": self.budget,
                "limit": self.limit, "admitted": self.admitted,
                "fits": self.fits}


def kernel_vmem_bytes(op: str, shape, dtype) -> int:
    """The scoped VMEM one compiled panel kernel allocates: its resident
    refs (the real ``pallas_call`` operands, outputs and scratch) plus
    the full-panel temporaries Mosaic stacks beside them.  The temporary
    counts are fitted to what the v5e compiler reported at the gate's
    corners (float32, PR 25):

    * ``lu_panel``: input + packed output, and about three more panels
      of temporaries -- 26.3 MiB for a (5456, 256) panel of 5.3 MiB;
    * ``potrf_inv``: D, L, Li at ``round_up(w, LANE)`` square plus two
      ``bs``-square scratch blocks, and about 1.5 squares of temporaries
      -- 19.9 MiB for a 1024-square block;
    * ``qr_panel``: input + packed output + about four panels of
      temporaries, plus the T, B and T^T squares -- 24.6 MiB for a
      (4096, 256) panel, 30.3 MiB for a 1024-square one.
    """
    z = np.dtype(dtype).itemsize
    m, w = int(shape[0]), int(shape[1])
    wp = round_up(w, LANE)
    if op == "cholesky":
        bs = min(512, wp)
        return (9 * wp * wp // 2 + 2 * bs * bs) * z
    mp = round_up(m, SUBLANE)
    if op == "lu":
        return 5 * max(mp, wp) * wp * z
    if op == "qr":
        return (6 * mp * wp + 3 * wp * wp) * z
    raise KeyError(f"no fused panel kernel for op {op!r}")


def check_panel_vmem(op: str, shape, dtype="float32", *,
                     budget: int = PANEL_VMEM_BUDGET,
                     limit: int = PANEL_VMEM_LIMIT) -> PanelVmemCheck:
    """Cross-check ONE panel shape: gate pricing vs kernel allocation.

    ``admitted`` reproduces :meth:`PanelPlan.use_pallas` exactly at the
    default budget (asserted by tests/analysis); ``fits`` is the truth
    the gate is supposed to imply: the compiled kernel stays inside the
    scoped-VMEM ``limit`` it is compiled with."""
    copies = PANEL_GATE_COPIES[op]
    z = np.dtype(dtype).itemsize
    mp = round_up(int(shape[0]), SUBLANE)
    np_ = round_up(int(shape[1]), LANE)
    gate = copies * mp * np_ * z
    kern = kernel_vmem_bytes(op, shape, dtype)
    return PanelVmemCheck(op=op, shape=tuple(int(s) for s in shape),
                          dtype=np.dtype(dtype).name, gate_bytes=gate,
                          kernel_bytes=kern, budget=int(budget),
                          limit=int(limit), admitted=gate <= budget,
                          fits=kern <= limit)


def panel_shapes(op: str, n: int, nb: int):
    """The panel shapes a blocked sweep of ``op`` at (n, nb) dispatches:
    tall (remaining-rows x block) panels for lu/qr, the (w, w) diagonal
    blocks for cholesky."""
    shapes = []
    for k in range(0, max(int(n), 1), max(int(nb), 1)):
        w = min(int(nb), int(n) - k)
        if w <= 0:
            break
        shapes.append((w, w) if op == "cholesky" else (int(n) - k, w))
    return shapes


def panel_vmem_checks(op: str, n: int, nb: int, dtype="float32", *,
                      budget: int = PANEL_VMEM_BUDGET):
    """Every dispatch-site cross-check of one blocked sweep."""
    return [check_panel_vmem(op, s, dtype, budget=budget)
            for s in panel_shapes(op, n, nb)]
