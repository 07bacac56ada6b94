"""Analytic cost model: score a knob configuration WITHOUT executing it.

Two ingredients, matching how the library's cost actually splits:

* **Communication** -- for the blocked factorizations and solves
  (``cholesky``/``lu``/``qr``/``trsm``/``herk``) the schedule is what the
  knobs change, so the model does not guess it: the candidate is traced
  ABSTRACTLY through the real driver (``jax.make_jaxpr`` on storage-form
  ``ShapeDtypeStruct`` inputs, exactly like :mod:`..analysis.drivers`) and
  the collective rounds/ring-model bytes are read off the resulting
  :class:`~elemental_tpu.analysis.plan.CommPlan`.  Problems larger than
  :data:`TRACE_REAL_LIMIT` are traced at a ratio-preserving scaled geometry
  (same schedule shape, capped step count) and extrapolated: latency
  scales with the real step count, bytes with the real matrix area.  For
  ``gemm`` the per-alg comm plans are closed-form ring-model site sums
  (the SUMMA panel schedules are simple enough to write down; the
  closed forms are cross-checked against the abstract traces in
  ``tests/tune``) so alg selection on the default ``alg='auto'`` hot path
  costs microseconds, never a trace.

* **Compute** -- an MXU-roofline flop term: ``flops / (p * peak)`` scaled
  by a blocksize-efficiency factor ``1 + HALF_NB/nb + IMB * nb/extent``
  (small panels starve the MXU; huge panels serialize the panel/diagonal
  work and unbalance the tail), which is what gives the nb sweep an
  interior optimum -- the same shape the A/B harness measures on real
  chips (nb=2048 at N=32k on v5e).

Everything runs cold on CPU (``'auto'`` with an empty cache never touches
a device), is deterministic, and is memoized per scaled trace geometry.
The model is a RANKING device: constants are first-order per-backend
defaults (override with ``machine=``), validated by the golden comm-plan
agreement tests rather than by absolute wall-clock accuracy.
"""
from __future__ import annotations

import dataclasses
import math

from .knobs import DEFAULT_CROSSOVER, TuneContext
from .policy import blocksize_policy

#: problems with sweep extent at or below this trace at their REAL
#: geometry (exact golden-comparable collective counts); larger ones trace
#: at a scaled geometry with at most _MAX_TRACE_STEPS blocked steps
TRACE_REAL_LIMIT = 96
_MAX_TRACE_STEPS = 6

#: blocksize-efficiency constants (see module docstring): HALF_NB is the
#: panel width at which MXU efficiency halves, IMB weights the serialized
#: panel/tail fraction nb/extent.  With the TPU machine model these place
#: the optimum at nb=2048 for N=32k, the value the benchmark's cells pass
#: (no other nb has a ledger line).
HALF_NB = 512.0
IMB = 3.0

#: the quantized-collective term (ISSUE 8): wire-byte scaling per
#: ``comm_precision`` mode.  bf16 is exactly half; int8 blends the ~4x
#: block-scaled gather family with the bf16-degraded pairs and the packed
#: scale rows, so 0.3 is the modeled blend (the traced *_commq golden
#: plans pin the exact per-driver ratios).
WIRE_FACTORS = {"bf16": 0.5, "int8": 0.3}

#: encode+decode vector passes over the LOGICAL payload per mode: bf16 is
#: one cast on each side; int8 adds the tile-amax reduction and the
#: scale multiply.  Priced against ``MachineModel.decode_bw_bytes_per_s``
#: so tiny latency-bound problems keep ``None`` (the candidate-order
#: tie-break) while bandwidth-bound geometries buy the narrower wire.
DECODE_PASSES = {"bf16": 2.0, "int8": 4.0}


@dataclasses.dataclass(frozen=True)
class MachineModel:
    """First-order per-backend constants for the scoring terms."""
    name: str
    latency_s: float           # per collective round (dispatch + hop)
    bw_bytes_per_s: float      # per-device collective bandwidth
    peak_flops: float          # per-device fp32-class matmul peak
    #: vector-unit (HBM-stream) bandwidth pricing the quantize/dequantize
    #: passes of the comm_precision path -- roughly 10x the wire
    decode_bw_bytes_per_s: float = 4.0e11
    #: per-device HBM capacity (ISSUE 18): candidates whose statically
    #: derived peak live bytes exceed it are PRUNED by the resolver, not
    #: merely penalized -- an OOM is not a slow configuration
    hbm_bytes: float = 16 * 2**30


MACHINES = {
    "tpu": MachineModel("tpu", latency_s=2e-6, bw_bytes_per_s=4.5e10,
                        peak_flops=3.0e13, hbm_bytes=16 * 2**30),
    "gpu": MachineModel("gpu", latency_s=3e-6, bw_bytes_per_s=3.0e10,
                        peak_flops=2.0e13, hbm_bytes=80 * 2**30),
    "cpu": MachineModel("cpu", latency_s=5e-6, bw_bytes_per_s=1.0e10,
                        peak_flops=2.0e11, hbm_bytes=64 * 2**30),
}


def machine_for(backend: str) -> MachineModel:
    """The constants row of ``backend``; a backend without a row is an
    error, never priced as some other machine."""
    try:
        return MACHINES[str(backend).lower()]
    except KeyError:
        raise ValueError(
            f"no machine model for backend {backend!r}; known: "
            f"{sorted(MACHINES)}") from None


@dataclasses.dataclass
class CostBreakdown:
    """One scored candidate, with the terms the ``explain`` CLI prints."""
    config: dict
    compute_s: float
    latency_s: float
    bandwidth_s: float
    rounds: float              # extrapolated collective rounds
    comm_bytes: float          # extrapolated ring-model WIRE bytes/device
    prim_counts: dict          # per-collective counts AT TRACE GEOMETRY
    detail: dict               # trace geometry / closed-form site notes
    pivot_s: float = 0.0       # pivot/reflector serial-chain latency
    decode_s: float = 0.0      # comm_precision encode/decode passes
    panel_impl_s: float = 0.0  # panel kernel-launch overhead (ISSUE 17)
    peak_bytes: float = 0.0    # statically derived per-device peak live
    pruned: bool = False       # peak_bytes > machine.hbm_bytes (OOM risk)

    @property
    def total_s(self) -> float:
        return self.compute_s + self.latency_s + self.bandwidth_s \
            + self.pivot_s + self.decode_s + self.panel_impl_s

    def to_doc(self) -> dict:
        return {"config": dict(self.config),
                "total_s": self.total_s, "compute_s": self.compute_s,
                "latency_s": self.latency_s, "bandwidth_s": self.bandwidth_s,
                "pivot_s": self.pivot_s, "decode_s": self.decode_s,
                "panel_impl_s": self.panel_impl_s,
                "rounds": self.rounds, "comm_bytes": self.comm_bytes,
                "peak_bytes": self.peak_bytes, "pruned": self.pruned,
                "prim_counts": dict(self.prim_counts),
                "detail": dict(self.detail)}


# ---------------------------------------------------------------------
# flop counts (LAPACK working notes; square getrf = 2n^3/3 etc.)
# ---------------------------------------------------------------------

def op_flops(op: str, dims) -> float:
    if op == "cholesky":
        n = dims[0]
        return n ** 3 / 3
    if op == "lu":
        m, n = dims[0], dims[-1]
        k = min(m, n)
        return 2 * (m * n * k - (m + n) * k * k / 2 + k ** 3 / 3)
    if op == "qr":
        m, n = dims[0], dims[-1]
        k = min(m, n)
        return 2 * k * k * (max(m, n) - k / 3)
    if op == "trsm":
        m, n = dims[0], dims[-1]
        return float(m) * m * n
    if op == "herk":
        m, k = dims[0], dims[-1]
        return float(m) * m * k
    if op == "gemm":
        m, k, n = dims
        return 2.0 * m * k * n
    raise KeyError(f"no flop formula for op {op!r}")


def _compute_seconds(op: str, ctx: TuneContext, nb, machine: MachineModel,
                     nb_sensitive: bool = True) -> float:
    p = ctx.grid_size
    base = op_flops(op, ctx.dims) / (p * machine.peak_flops)
    if not nb_sensitive:
        return base
    ext = max(ctx.extent, 1)
    nb_r = blocksize_policy(nb, ctx.grain, ext)
    return base * (1.0 + HALF_NB / nb_r + IMB * nb_r / ext)


def _pivot_seconds(op: str, ctx: TuneContext, config: dict,
                   machine: MachineModel) -> float:
    """Pivot/reflector serial-chain latency: the term that differentiates
    the panel strategies (ISSUE 6).

    The classic panels of lu/qr run one data-dependent step PER COLUMN
    over the full panel height -- an ``extent``-deep serial chain the MXU
    roofline term cannot see.  The tree panels (CALU tournament / TSQR)
    split that chain across the ``r`` grid rows (depth ``extent / r``)
    and add ``ceil(log2 r)`` pairwise playoff/reduction rounds per panel.
    Each unit of chain depth is priced at one ``machine.latency_s`` -- a
    RANKING device like the rest of the model: on single-row grids both
    strategies price identically (the slab IS the panel) and the
    candidate order's classic-first tie-break keeps the baseline."""
    if op not in ("lu", "qr"):
        return 0.0
    ext = max(ctx.extent, 1)
    unit = machine.latency_s
    panel = config.get("panel") or "classic"
    r = ctx.grid_shape[0]
    if panel == "classic" or r <= 1:
        return ext * unit
    nb_r = blocksize_policy(config.get("nb"), ctx.grain, ext)
    steps = max(1, math.ceil(ext / nb_r))
    return (ext / r) * unit + steps * math.ceil(math.log2(r)) * unit


#: interpret-mode slowdown of a pallas_call off-TPU: the fused panel
#: kernels run through the Pallas interpreter there (an eval_jaxpr walk,
#: orders of magnitude off compiled XLA), so 'auto' must never pick
#: 'pallas' on cpu/gpu.  50x is a deliberately blunt ranking constant --
#: any value >> 1 yields the same winner (pinned by tests/tune).
INTERPRET_PENALTY = 50.0


def _panel_impl_seconds(op: str, ctx: TuneContext, config: dict,
                        machine: MachineModel) -> float:
    """Panel kernel-LAUNCH overhead: the term that differentiates the
    panel implementations (ISSUE 17).

    The XLA panel ladder lowers to one data-dependent op chain PER
    COLUMN of the sweep (``extent`` launches of pivot/scale/update for
    lu, larfg steps for qr, per-block potrf/trinv pairs for cholesky)
    -- launch-latency work the flop roofline cannot see.  The fused
    Pallas kernel pays ONE launch per nb-panel (``steps`` total) and
    runs the column chain VMEM-resident, so on TPU
    ``panel_impl='auto'`` resolves to 'pallas'.  Off-TPU the kernels
    only exist in interpret mode, priced at :data:`INTERPRET_PENALTY`
    times the ladder -- 'auto' stays on 'xla' there.  Like
    ``_pivot_seconds`` this is a ranking device, not a wall-clock
    prediction; per-column units are one ``machine.latency_s``."""
    if op not in ("lu", "cholesky", "qr"):
        return 0.0
    ext = max(ctx.extent, 1)
    unit = machine.latency_s
    impl = config.get("panel_impl") or "xla"
    if impl != "pallas":
        return ext * unit
    if ctx.backend != "tpu":
        return ext * unit * INTERPRET_PENALTY
    nb_r = blocksize_policy(config.get("nb"), ctx.grain, ext)
    return max(1, math.ceil(ext / nb_r)) * unit


# ---------------------------------------------------------------------
# traced comm term (cholesky / lu / qr / trsm / herk)
# ---------------------------------------------------------------------

_TRACE_MEMO: dict = {}


def clear_trace_memo() -> None:
    _TRACE_MEMO.clear()


def _quant(v: float, grain: int, lo: int) -> int:
    from ..core.view import round_up
    return max(round_up(max(int(round(v)), 1), grain), lo)


def _geometry(ctx: TuneContext, nb, crossover, lookahead):
    """(trace dims, nb_t, xover_t, lat_scale, byte_scale) for the candidate.

    Small problems trace at their REAL geometry (exact counts, directly
    comparable to the golden comm plans).  Large ones keep the schedule
    shape but cap the step count: nb_t ~ 16 (grain-aligned), the crossover
    threshold maps to the same FRACTION of the sweep, latency extrapolates
    with the real step count and bytes with the real area (one full
    panel sweep moves O(area) words regardless of nb).
    """
    grain = ctx.grain
    ext = max(ctx.extent, 1)
    nb_r = blocksize_policy(nb, grain, ext)
    steps_real = max(1, math.ceil(ext / nb_r))
    xo = crossover
    if xo is None:
        xo = DEFAULT_CROSSOVER if lookahead else 0
    if ext <= TRACE_REAL_LIMIT:
        dims_t = tuple(ctx.dims)
        return dims_t, nb_r, int(xo), 1.0, 1.0
    steps_t = min(steps_real, _MAX_TRACE_STEPS)
    nb_t = _quant(16, grain, grain)
    ext_t = nb_t * steps_t
    scale = ext_t / ext
    dims_t = tuple(ext_t if d == ext else _quant(d * scale, grain, nb_t)
                   for d in ctx.dims)
    frac = min(float(xo) / ext, 1.0) if xo else 0.0
    xo_t = nb_t * int(round(frac * steps_t))
    lat_scale = steps_real / steps_t
    area = 1.0
    for d_r, d_t in zip(ctx.dims, dims_t):
        area *= d_r / d_t
    return dims_t, nb_t, xo_t, lat_scale, area


def _trace_stats(op: str, dims_t, nb_t: int, la, xo_t, grid, dtype,
                 panel: str = "classic", redist_path=None):
    """Abstract-trace ``op`` at the scaled geometry; totals memoized."""
    key = (op, dims_t, nb_t, bool(la), int(xo_t),
           (grid.height, grid.width), str(dtype), panel, redist_path)
    hit = _TRACE_MEMO.get(key)
    if hit is not None:
        return hit
    import jax
    import jax.numpy as jnp
    from ..core.dist import Dist
    from ..core.distmatrix import DistMatrix
    from ..analysis.drivers import storage_shape, trace_callable

    MC, MR = Dist.MC, Dist.MR

    def inp(m, n):
        return jax.ShapeDtypeStruct(storage_shape(m, n, MC, MR, grid), dtype)

    def dm(a, m, n):
        return DistMatrix(a, (m, n), MC, MR, 0, 0, grid)

    if op == "cholesky":
        n = dims_t[0]

        def fn(a):
            from ..lapack.cholesky import cholesky
            return cholesky(dm(a, n, n), nb=nb_t, lookahead=la, crossover=xo_t,
                            redist_path=redist_path)
        args = (inp(n, n),)
    elif op == "lu":
        m, n = dims_t[0], dims_t[-1]

        def fn(a):
            from ..lapack.lu import lu
            return lu(dm(a, m, n), nb=nb_t, lookahead=la, crossover=xo_t,
                      panel=panel, redist_path=redist_path)
        args = (inp(m, n),)
    elif op == "qr":
        m, n = dims_t[0], dims_t[-1]

        def fn(a):
            from ..lapack.qr import qr
            return qr(dm(a, m, n), nb=nb_t, panel=panel,
                      redist_path=redist_path)
        args = (inp(m, n),)
    elif op == "trsm":
        m, n = dims_t[0], dims_t[-1]

        def fn(a, b):
            from ..blas.level3 import trsm
            return trsm("L", "L", "N", dm(a, m, m), dm(b, m, n), nb=nb_t,
                        redist_path=redist_path)
        args = (inp(m, m), inp(m, n))
    elif op == "herk":
        m, k = dims_t[0], dims_t[-1]

        def fn(a):
            from ..blas.level3 import herk
            return herk("L", dm(a, m, k), nb=nb_t, redist_path=redist_path)
        args = (inp(m, k),)
    else:
        raise KeyError(f"no trace builder for op {op!r}")

    plan, closed, log = trace_callable(fn, args, name=f"tune:{op}",
                                       grid=grid)
    totals = plan.totals()
    # the memory term (ISSUE 18) rides the SAME abstract trace: the
    # liveness walk + replicated census of analysis.memory, at the trace
    # geometry (extrapolated with byte_scale by the caller, like bytes)
    from ..analysis.memory import analyze_jaxpr, replication_census
    p = max(grid.height * grid.width, 1)
    walk = analyze_jaxpr(closed, grid_size=p)
    census = replication_census(log, (grid.height, grid.width))
    # latency rounds count only REAL collectives: a collective over a
    # size-1 axis (1x1 grids, degenerate sub-axes) is elided by XLA.
    # prim_counts keep the raw per-primitive totals -- those are what the
    # golden comm-plan snapshots pin.
    stats = {"totals": totals,
             "rounds": sum(ev.count for ev in plan.events
                           if ev.axis_size > 1),
             "bytes": sum(t["bytes"] for t in totals.values()),
             "peak": walk.peak_bytes + walk.nonstatic_peak_bytes
             + census["max_extra_bytes"]}
    _TRACE_MEMO[key] = stats
    return stats


def _wire_terms(cbytes: float, comm_precision, machine: MachineModel):
    """(wire bytes, decode seconds) of the comm_precision term: the
    bytes-on-wire shrink by the mode's factor while an encode/decode
    vector pass over the LOGICAL payload is added on each side."""
    if not comm_precision:
        return cbytes, 0.0
    wire = cbytes * WIRE_FACTORS.get(comm_precision, 1.0)
    decode = DECODE_PASSES.get(comm_precision, 0.0) * cbytes \
        / machine.decode_bw_bytes_per_s
    return wire, decode


def _traced_cost(op: str, config: dict, ctx: TuneContext, grid, dtype,
                 machine: MachineModel) -> CostBreakdown:
    la = config.get("lookahead", True)
    xo = config.get("crossover")
    nb = config.get("nb")
    panel = config.get("panel") or "classic"
    cpm = config.get("comm_precision")
    # redist_path (ISSUE 12/13) reaches the traced driver, so the direct
    # route's collective counts/bytes are read off its REAL schedule --
    # the "one a2a round vs k gather rounds" term is the trace itself.
    rp = config.get("redist_path") \
        if op in ("lu", "cholesky", "qr", "trsm", "herk") else None
    # panel_impl deliberately does NOT reach _trace_stats: panels are
    # replicated-local compute, so the traced comm schedule is identical
    # under either implementation (the comm-invariance gate of
    # tools/check.sh kernels pins exactly this) -- keeping it out of the
    # memo key shares one trace across the panel_impl sweep.
    dims_t, nb_t, xo_t, lat_scale, byte_scale = _geometry(ctx, nb, xo, la)
    stats = _trace_stats(op, dims_t, nb_t, la, xo_t, grid, dtype, panel, rp)
    rounds = stats["rounds"] * lat_scale
    cbytes = stats["bytes"] * byte_scale
    wire_bytes, decode_s = _wire_terms(cbytes, cpm, machine)
    # resident bytes extrapolate with the matrix AREA like wire bytes
    # (the peak is operand-slab dominated, not schedule dominated)
    peak = stats["peak"] * byte_scale
    return CostBreakdown(
        config=dict(config),
        compute_s=_compute_seconds(op, ctx, nb, machine),
        latency_s=machine.latency_s * rounds,
        bandwidth_s=wire_bytes / machine.bw_bytes_per_s,
        pivot_s=_pivot_seconds(op, ctx, config, machine),
        decode_s=decode_s,
        panel_impl_s=_panel_impl_seconds(op, ctx, config, machine),
        rounds=rounds, comm_bytes=wire_bytes,
        peak_bytes=peak, pruned=peak > machine.hbm_bytes,
        prim_counts={k: t["count"] for k, t in stats["totals"].items()},
        detail={"trace_dims": list(dims_t), "trace_nb": nb_t,
                "trace_crossover": xo_t, "lat_scale": round(lat_scale, 3),
                "byte_scale": round(byte_scale, 3), "panel": panel,
                "comm_precision": cpm, "redist_path": rp})


# ---------------------------------------------------------------------
# closed-form gemm comm plans (ring model per SUMMA schedule)
# ---------------------------------------------------------------------

def _gemm_sites(alg: str, m: int, k: int, n: int, r: int, c: int,
                nb, itemsize: int, grain_lcm: int, redist_path=None):
    """(site list, rounds, bytes) for one SUMMA schedule.

    Per-device ring-model received bytes (cf. ``analysis.jaxpr_walk
    .estimate_bytes``): all_gather of a local block of B bytes over S
    ranks costs B*(S-1); a psum costs 2*B*(S-1)/S.  Panel loops use the
    same ``blocksize_policy`` grains as the drivers, so panel counts match
    the traced schedules.

    With ``redist_path='direct'`` the operand moves the drivers route
    through the one-shot plan compiler (ISSUE 12) are priced off the
    compiled :class:`~..redist.plan.RedistPlan` instead -- exactly one
    collective (or zero, when the plan is local) at the plan's honest
    padded wire bytes.  ``redist_path=None`` keeps this closed form
    byte-identical (pinned against the abstract trace by tests/tune).
    """
    p = r * c
    z = itemsize
    sites = []

    def ag(tag, local_elems, s):
        if s > 1:
            sites.append((tag, "all_gather", local_elems * z * (s - 1)))

    def ps(tag, local_elems, s):
        if s > 1:
            sites.append((tag, "psum", 2 * local_elems * z * (s - 1) // s))

    def direct(tag, src_pair, dst_pair, gshape):
        from ..redist.plan import compile_plan
        plan = compile_plan(src_pair, dst_pair, gshape, (r, c))
        if plan is None or plan.kind == "local":
            return                          # zero collective rounds
        prim = "all_to_all" if plan.kind == "a2a" else "ppermute"
        sites.append((tag, prim, plan.wire_bytes(z)))

    use_direct = redist_path == "direct" and p > 1
    from ..core.dist import MC, MR, VC, STAR  # jax-free taxonomy

    if alg == "C":
        kb = blocksize_policy(nb, grain_lcm, k)
        panels = max(1, math.ceil(k / kb))
        for _ in range(panels):
            if use_direct:
                direct("A1->[MC,*]", (MC, MR), (MC, STAR), (m, kb))
                direct("B1->[*,MR]", (MC, MR), (STAR, MR), (kb, n))
            else:
                ag("A1->[MC,*]", (m / r) * (kb / c), c)
                ag("B1->[*,MR]", (kb / r) * (n / c), r)
    elif alg == "A":
        jb = blocksize_policy(nb, c, n)
        panels = max(1, math.ceil(n / jb))
        for _ in range(panels):
            if use_direct:
                direct("B1->[MR,*]", (MC, MR), (MR, STAR), (k, jb))
            else:
                ag("B1->[MR,*]", (k / c) * (jb / r), r)  # gather over mc
            ps("D1 psum(mr)", (m / r) * jb, c)
            ag("D1->[MC,MR]", (m / r) * (jb / c), 1 if c == 1 else 2)
    elif alg == "B":
        ib = blocksize_policy(nb, r, m)
        panels = max(1, math.ceil(m / ib))
        for _ in range(panels):
            if use_direct:
                direct("A1^T->[MC,*]", (MR, MC), (MC, STAR), (k, ib))
            else:
                ag("A1^T->[MC,*]", (k / r) * (ib / c), c)
            ps("D1 psum(mc)", (ib / c) * n, r)
            ag("D1->[MC,MR]", (ib / r) * (n / c), 1 if r == 1 else 2)
    elif alg == "dot":
        if p > 1:
            if use_direct:
                direct("A->[*,VC]", (MC, MR), (STAR, VC), (m, k))
                direct("B->[VC,*]", (MC, MR), (VC, STAR), (k, n))
            else:
                ag("A->[*,VC]", m * (k / p), 2)          # cyclic re-land
                ag("B->[VC,*]", (k / p) * n, 2)
            ps("D psum(all)", m * n, p)
            ag("D filter", (m / r) * (n / c), 1)
    elif alg == "gspmd":
        ag("B->[MR,*]", (k / c) * (n / r), r)
        ps("D psum(mr)", (m / r) * n, c)
        ag("D->[MC,MR]", (m / r) * (n / c), 1 if c == 1 else 2)
    elif alg == "slice":
        # Slicing gemm (ISSUE 16): three single-collective hops.  They
        # run the engine's fused kernels (one all-gather to [STAR,STAR],
        # one all-to-all over one mesh axis for each [V] leg), which ship
        # the wire bytes of the compiled RedistPlan of the same pair to
        # the byte, ragged extents included (tests/analysis pins it), so
        # the plan's byte math prices them -- regardless of redist_path
        # (the route takes no such knob, so the crossing prices
        # identically and the tie-break keeps the default).  No hidden
        # psum: k is unsharded on both sides of the local contraction.
        if p > 1:
            from ..redist.plan import gemm_slice_plans
            for tag, plan in gemm_slice_plans(m, k, n, (r, c))[1]:
                if plan is None or plan.kind == "local":
                    continue                # degenerate relabeling leg
                prim = "all_gather" if plan.dst == (STAR, STAR) \
                    else "all_to_all"
                sites.append((tag, prim, plan.wire_bytes(z)))
    else:
        raise KeyError(f"unknown gemm alg {alg!r}")
    rounds = len(sites)
    total = int(sum(s[2] for s in sites))
    return sites, rounds, total


def _gemm_cost(config: dict, ctx: TuneContext, itemsize: int,
               machine: MachineModel) -> CostBreakdown:
    m, k, n = ctx.dims
    r, c = ctx.grid_shape
    alg = config["alg"]
    nb = config.get("nb")
    cpm = config.get("comm_precision")
    rp = config.get("redist_path")
    sites, rounds, cbytes = _gemm_sites(alg, m, k, n, r, c, nb, itemsize,
                                        ctx.grain, redist_path=rp)
    counts: dict = {}
    for _, prim, b in sites:
        if b > 0:
            counts[prim] = counts.get(prim, 0) + 1
    # the engine quantizes the redistribution collectives (gathers on the
    # chain, the one-shot a2a/ppermute payloads on the direct route);
    # GSPMD-inserted contraction psums stay full precision (gemm's non-SS
    # pairs all degrade int8 -> bf16, so both modes price at bf16)
    ag_bytes = sum(b for _, p, b in sites
                   if p in ("all_gather", "all_to_all", "ppermute"))
    wire_ag, decode_s = _wire_terms(ag_bytes,
                                    "bf16" if cpm else None, machine)
    wire_bytes = (cbytes - ag_bytes) + wire_ag
    # closed-form peak (ISSUE 18): the three operands sharded over p,
    # plus the largest single gathered/reduced buffer a site stages (a
    # collective's received bytes land in one live replicated form) --
    # the same ranking-device spirit as the rest of the model, pinned
    # within 2x of the abstract-trace walk by tests/tune
    p_dev = max(r * c, 1)
    base = (m * k + k * n + m * n) * itemsize / p_dev
    peak = base + max((b for _, _, b in sites), default=0)
    return CostBreakdown(
        config=dict(config),
        compute_s=_compute_seconds("gemm", ctx, nb, machine,
                                   nb_sensitive=alg in ("A", "B", "C")),
        latency_s=machine.latency_s * rounds,
        bandwidth_s=wire_bytes / machine.bw_bytes_per_s,
        decode_s=decode_s,
        rounds=rounds, comm_bytes=wire_bytes, prim_counts=counts,
        peak_bytes=peak, pruned=peak > machine.hbm_bytes,
        detail={"sites": [{"site": t, "prim": p, "bytes": b}
                          for t, p, b in sites],
                "comm_precision": cpm, "redist_path": rp})


# ---------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------

def score_config(op: str, config: dict, *, ctx: TuneContext, grid=None,
                 dtype=None, machine: MachineModel | None = None
                 ) -> CostBreakdown:
    """Score one candidate configuration of ``op`` at ``ctx``.

    ``grid``/``dtype`` (a live Grid and a jnp dtype) are required for the
    traced ops; gemm scores purely from ``ctx`` and the dtype itemsize.
    """
    machine = machine or machine_for(ctx.backend)
    if op == "gemm":
        import numpy as np
        itemsize = np.dtype(dtype if dtype is not None else "float32").itemsize
        return _gemm_cost(config, ctx, itemsize, machine)
    if grid is None or dtype is None:
        raise ValueError(f"scoring {op!r} needs a live grid and dtype "
                         "(the comm term traces the real driver)")
    return _traced_cost(op, config, ctx, grid, dtype, machine)
