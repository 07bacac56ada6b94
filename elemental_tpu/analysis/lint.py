"""Rule-based lints over a comm plan + redistribution trace.

Each rule inspects the statically extracted comm schedule (the jaxpr-level
:class:`~elemental_tpu.analysis.jaxpr_walk.CollectiveEvent` list and/or
the engine's :class:`~elemental_tpu.redist.engine.RedistRecord` log) and
reports :class:`LintFinding` objects.  Rules:

  EL001 fuse-adjacent-gathers   two back-to-back redistributions of the
        SAME [VC,STAR]/[STAR,VC] panel onto the [MC,STAR]+[STAR,MR]
        operand pair -- the exact shape :func:`panel_spread` fuses into
        one collective round (cholesky/herk's trailing chain pre-PR2).
  EL002 redundant-round-trip    a redistribution whose output is fed
        UNTOUCHED (same object -- provably no intervening compute) into a
        redistribution straight back to the source distribution: the pair
        is a no-op costing two collective rounds.  The finding also
        carries the one-shot rewrite (ISSUE 12): its ``fix_hint`` quotes
        the equivalent compiled direct plan -- src->dst, plan kind,
        round count, ring-model byte estimate vs the chain's -- and
        ``perf/comm_audit lint --fix-hint`` prints it.
  EL003 loop-invariant-collective   a collective inside a scan/while body
        whose operands derive only from loop constants -- hoistable.
  EL004 f64-promotion           a collective moving float64/complex128
        bytes in a program traced from <=32-bit inputs: an unintended
        promotion doubling wire bytes (x64 mode makes these easy to leak).
  EL005 bf16-leak               a collective moving bfloat16 outside the
        opt-in ``update_precision`` paths (``allow_bf16`` in the driver
        spec): bf16 on the wire silently halves mantissa everywhere.

Memory rules (ISSUE 18) run over a ``memory_plan/v1``
:class:`~elemental_tpu.analysis.memory.MemoryPlan` via :func:`lint_memory`:

  EL006 peak-over-budget        statically derived per-device peak live
        bytes exceed the driver's declared budget
        (``DriverSpec.mem_budget_factor`` x input+output residency) --
        catches crossover/slice gathers that silently materialize the
        full matrix.  ``while``-body allocations have no static trip
        count; they are excluded from the pinned peak but FOLDED INTO
        this check, so non-static growth still surfaces in lint.
  EL007 vmem-overflow           a PanelPlan pallas dispatch whose gate
        pricing (``use_pallas``: copies x tile-padded bytes) admits a
        panel whose ACTUAL kernel allocation (real pallas_call
        out_shapes + carries, incl. square LANE padding) overflows the
        VMEM budget -- the 16 MiB fallback gate proven, not trusted.
  EL008 missing-donation        a jitted entry whose output aval matches
        an UNDONATED input aval: the buffer could be donated
        (``donate_argnums``) to halve residency.  Only checked when the
        plan's meta declares its donation set (``meta["donated"]``) --
        the serve ``__donated`` exec-cache path becomes lintable
        instead of a convention.
  EL009 double-materialization  two or more full-matrix ([STAR,STAR])
        gathers of the SAME source operand: ``p`` live replicas paid
        repeatedly for one global operand.

``lint_plan`` returns findings sorted by rule id; an empty list means the
plan is clean (the ``perf/comm_audit.py lint`` CLI exits non-zero on any
finding).
"""
from __future__ import annotations

import dataclasses

from .jaxpr_walk import find_loop_invariant_collectives

_NARROW = ("float16", "bfloat16", "float32", "complex64", "int32", "int16",
           "int8", "uint32", "uint16", "uint8", "bool")
_WIDE = ("float64", "complex128")


@dataclasses.dataclass(frozen=True)
class LintFinding:
    rule: str          # "EL00x"
    name: str          # short rule slug
    message: str       # human-readable, names the offending site
    severity: str = "warning"
    fix_hint: str = "" # concrete rewrite suggestion (lint --fix-hint)

    def __str__(self):
        return f"{self.rule} [{self.name}] {self.message}"


# ---------------------------------------------------------------------
# individual rules
# ---------------------------------------------------------------------

def _is_v_panel(dist) -> bool:
    names = tuple(d.value for d in dist)
    return names in (("VC", "STAR"), ("STAR", "VC"),
                     ("VR", "STAR"), ("STAR", "VR"))


def _spread_target(dist) -> bool:
    names = tuple(d.value for d in dist)
    return names in (("MC", "STAR"), ("STAR", "MR"),
                     ("MR", "STAR"), ("STAR", "MC"))


def rule_fuse_adjacent_gathers(plan, redist_log) -> list:
    """EL001: the panel + its adjoint spread issued as separate calls."""
    out = []
    recs = [r for r in redist_log if r.kind == "redistribute"]
    for a, b in zip(recs, recs[1:]):
        if not (_is_v_panel(a.src) and _spread_target(a.dst)):
            continue
        if not (_is_v_panel(b.src) and _spread_target(b.dst)):
            continue
        if a.dst == b.dst:
            continue
        # same panel extents (the adjoint chain transposes the gshape)
        if a.gshape not in (b.gshape, b.gshape[::-1]):
            continue
        out.append(LintFinding(
            "EL001", "fuse-adjacent-gathers",
            f"adjacent panel spreads {a.label} then {b.label} on a "
            f"{a.gshape} panel: fuse into one panel_spread() round "
            f"(one all_gather instead of separate gather chains)"))
    return out


def _slice_rewrite_hint(rec, z: int) -> str:
    """The sub-range refinement of the EL002 rewrite (ISSUE 18): when the
    src->dst pair is slice-legal, quote the ``compile_slice_plan`` of a
    representative half-row-range so blocked consumers see that gathering
    ONLY the block they touch is a compilable one-shot, not a
    full-matrix-endpoint detour."""
    from ..redist.plan import compile_slice_plan
    gs = tuple(rec.grid_shape)
    m, n = rec.gshape
    rows = (0, max(int(m) // 2, 1))
    try:
        splan = compile_slice_plan(rec.src, rec.dst, rec.gshape, gs,
                                   rows=rows)
    except (ValueError, KeyError):
        return ""
    if splan is None:
        return ""
    return (f"; consuming a sub-range only? compile_slice_plan(src, dst, "
            f"{tuple(rec.gshape)}, {gs}, rows={rows}) one-shots the "
            f"A[{rows[0]}:{rows[1]}, :] slice as a '{splan.kind}' plan = "
            f"{splan.rounds} round(s) / ~{splan.wire_bytes(z)} B -- "
            f"pay for the block you touch, not the matrix")


def _direct_rewrite_hint(rec) -> str:
    """The one-shot rewrite of one chained leg (ISSUE 12): compile the
    src->dst direct plan and quote rounds/bytes next to the chain's;
    slice-legal pairs additionally quote the sub-range rewrite
    (ISSUE 18)."""
    gs = tuple(rec.grid_shape or ())
    if len(gs) != 2:
        return ""
    import numpy as np
    from ..redist.plan import compile_plan
    from ..redist.engine import chain_cost
    plan = compile_plan(rec.src, rec.dst, rec.gshape, gs)
    if plan is None:
        return ""
    z = np.dtype(rec.dtype).itemsize
    rounds_c, bytes_c = chain_cost(rec.src, rec.dst, rec.gshape, gs, z)
    return (f"if the {rec.dst[0].value}/{rec.dst[1].value} form is "
            f"actually consumed, route it as redistribute(..., "
            f"path='direct'): one-shot '{plan.kind}' plan for "
            f"{rec.label} at {rec.gshape} on {gs[0]}x{gs[1]} = "
            f"{plan.rounds} round(s) / ~{plan.wire_bytes(z)} B vs the "
            f"chain's {rounds_c} round(s) / ~{bytes_c} B; otherwise "
            f"delete both legs" + _slice_rewrite_hint(rec, z))


def rule_redundant_round_trip(plan, redist_log) -> list:
    """EL002: A->X then X->A on the untouched intermediate."""
    out = []
    recs = [r for r in redist_log if r.kind == "redistribute"]
    by_out = {}
    for r in recs:
        for oid in r.out_ids:
            by_out[oid] = r
    for r in recs:
        prev = by_out.get(r.in_id)
        if prev is None or prev is r:
            continue
        if prev.src == r.dst and prev.dst == r.src \
                and prev.gshape == r.gshape:
            out.append(LintFinding(
                "EL002", "redundant-round-trip",
                f"{prev.label} then {r.label} on the SAME untouched "
                f"{r.gshape} operand: the round trip is a no-op costing "
                f"two redistribution rounds",
                fix_hint=_direct_rewrite_hint(prev)))
    return out


def rule_loop_invariant(plan, closed_jaxpr=None) -> list:
    """EL003: hoistable collectives inside scan/while bodies."""
    if closed_jaxpr is None:
        return []
    out = []
    for prim, path in find_loop_invariant_collectives(closed_jaxpr):
        where = "/".join(path) or "<top>"
        out.append(LintFinding(
            "EL003", "loop-invariant-collective",
            f"{prim} inside {where} has loop-invariant operands: "
            f"hoist it out of the loop body"))
    return out


def rule_f64_promotion(plan) -> list:
    """EL004: wide dtypes on the wire from narrow inputs."""
    in_dtypes = plan.meta.get("input_dtypes") or [plan.meta.get("dtype")]
    if any(str(d) in _WIDE for d in in_dtypes if d):
        return []          # wide inputs: wide collectives are intended
    out = []
    seen = set()
    for ev in plan.events:
        if ev.dtype in _WIDE and (ev.prim, ev.dtype, ev.shape) not in seen:
            seen.add((ev.prim, ev.dtype, ev.shape))
            out.append(LintFinding(
                "EL004", "f64-promotion",
                f"{ev.prim} moves {ev.dtype} {ev.shape} at "
                f"{'/'.join(ev.path)} but the traced inputs are "
                f"{[str(d) for d in in_dtypes]}: unintended promotion "
                f"doubles wire bytes"))
    return out


def rule_bf16_leak(plan) -> list:
    """EL005: bf16 collectives without the update_precision opt-in."""
    if plan.meta.get("allow_bf16"):
        return []
    out = []
    seen = set()
    for ev in plan.events:
        if ev.dtype == "bfloat16" and (ev.prim, ev.shape) not in seen:
            seen.add((ev.prim, ev.shape))
            out.append(LintFinding(
                "EL005", "bf16-leak",
                f"{ev.prim} moves bfloat16 {ev.shape} at "
                f"{'/'.join(ev.path)} without the update_precision "
                f"opt-in: bf16 on the wire halves mantissa silently"))
    return out


def lint_plan(plan, redist_log=(), closed_jaxpr=None) -> list:
    """Run every rule; findings sorted by rule id (empty == clean)."""
    findings = []
    findings += rule_fuse_adjacent_gathers(plan, redist_log)
    findings += rule_redundant_round_trip(plan, redist_log)
    findings += rule_loop_invariant(plan, closed_jaxpr)
    findings += rule_f64_promotion(plan)
    findings += rule_bf16_leak(plan)
    return sorted(findings, key=lambda f: (f.rule, f.message))


# ---------------------------------------------------------------------
# memory rules (ISSUE 18) -- over a memory_plan/v1 MemoryPlan
# ---------------------------------------------------------------------

def rule_mem_budget(mplan, budget_factor: float) -> list:
    """EL006: peak live bytes over the declared per-driver budget."""
    base = mplan.stats.args_bytes + mplan.stats.outs_bytes
    budget = int(budget_factor * max(base, 1))
    ns = mplan.stats.nonstatic_peak_bytes
    total = mplan.peak_bytes + ns
    if total <= budget:
        return []
    at = "/".join(mplan.stats.peak_path) or "<top>"
    msg = (f"{mplan.driver} on {mplan.grid[0]}x{mplan.grid[1]}: peak live "
           f"{total} B exceeds the declared budget {budget} B "
           f"({budget_factor:g}x the {base} B input+output residency); "
           f"high-water at {at} ({mplan.stats.peak_prim})")
    if ns:
        msg += (f"; {ns} B of that sits inside while bodies with NO "
                f"static trip count (excluded from the golden peak, "
                f"folded into this check)")
    return [LintFinding(
        "EL006", "peak-over-budget", msg,
        fix_hint=(f"either the driver legitimately stages this much "
                  f"(raise MEM_BUDGET_FACTORS[{mplan.driver!r}] in "
                  f"analysis/drivers.py and say why) or a gather is "
                  f"materializing more than its consumer touches -- "
                  f"check the replicated census "
                  f"({mplan.replicated.get('count', 0)} site(s), max "
                  f"extra {mplan.replicated.get('max_extra_bytes', 0)} B)"))]


def rule_vmem_overflow(panel_checks) -> list:
    """EL007: gate-admitted panels whose compiled kernel would overflow
    the scoped-VMEM limit it is compiled with."""
    out = []
    seen = set()
    for chk in panel_checks:
        if not chk.overflow or (chk.op, chk.shape) in seen:
            continue
        seen.add((chk.op, chk.shape))
        out.append(LintFinding(
            "EL007", "vmem-overflow",
            f"{chk.op} panel {chk.shape} {chk.dtype}: use_pallas prices "
            f"{chk.gate_bytes} B (admitted, budget {chk.budget} B) but "
            f"the fused kernel actually allocates {chk.kernel_bytes} B "
            f"against a scoped limit of {chk.limit} B -- the gate would "
            f"dispatch a kernel the compiler refuses",
            severity="error",
            fix_hint=(f"raise the copies= the dispatch site passes to "
                      f"use_pallas so the gate prices >= "
                      f"{chk.kernel_bytes} B, or shrink the kernel's "
                      f"scratch residents")))
    return out


def rule_missing_donation(mplan, closed_jaxpr) -> list:
    """EL008: an output aval matching an undonated input aval.

    Opt-in: only runs when the plan's meta DECLARES its donation set
    (``meta["donated"]`` = iterable of donated arg positions; absent
    meta means the entry never claimed jit-with-donation semantics)."""
    donated = mplan.meta.get("donated")
    if donated is None or closed_jaxpr is None:
        return []
    donated = set(int(i) for i in donated)
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)

    def _sig(v):
        aval = getattr(v, "aval", None)
        shape = getattr(aval, "shape", None)
        dtype = getattr(aval, "dtype", None)
        if shape is None or dtype is None:
            return None
        return (tuple(shape), str(dtype))

    out_sigs = [s for s in (_sig(v) for v in jaxpr.outvars) if s]
    findings = []
    for i, v in enumerate(jaxpr.invars):
        if i in donated:
            continue
        sig = _sig(v)
        if sig and sig in out_sigs:
            findings.append(LintFinding(
                "EL008", "missing-donation",
                f"{mplan.driver}: input {i} {sig[0]} {sig[1]} matches an "
                f"output aval but is not in the donated set "
                f"{sorted(donated)}: the buffer is held live across the "
                f"whole call for nothing",
                fix_hint=f"add {i} to donate_argnums (XLA reuses the "
                         f"input buffer for the matching output, halving "
                         f"this operand's residency)"))
    return findings


def rule_double_materialization(mplan, redist_log) -> list:
    """EL009: >= 2 full-matrix gathers of the SAME source operand."""
    by_src = {}
    for rec in redist_log:
        if rec.kind != "redistribute":
            continue
        names = tuple(d.value for d in rec.dst)
        if names != ("STAR", "STAR"):
            continue
        by_src.setdefault((rec.in_id, rec.gshape, rec.dtype),
                          []).append(rec)
    out = []
    for (in_id, gshape, dtype), recs in sorted(
            by_src.items(), key=lambda kv: repr(kv[0][1:])):
        if len(recs) < 2:
            continue
        p = 1
        gs = tuple(recs[0].grid_shape or ())
        if len(gs) == 2:
            p = max(gs[0] * gs[1], 1)
        out.append(LintFinding(
            "EL009", "double-materialization",
            f"{len(recs)} separate [*,*] gathers of the SAME {gshape} "
            f"{dtype} operand: each keeps {p} live replicas per grid -- "
            f"gather once and reuse the replicated form",
            fix_hint="hoist the redistribute(.., STAR, STAR) above the "
                     "consumers (or thread the gathered operand through) "
                     "so the full-matrix materialization is paid once"))
    return out


def lint_memory(mplan, redist_log=(), closed_jaxpr=None,
                budget_factor: float = None, panel_checks=None) -> list:
    """Run the memory rules over one :class:`MemoryPlan`.

    ``budget_factor`` defaults to the registry's declared factor for the
    driver (4.0 when the driver is unregistered); ``panel_checks``
    defaults to the EL007 sweep of the driver's own panel schedule when
    its op has a fused kernel (driver name prefix lu/cholesky/qr + n/nb
    from the plan meta)."""
    if budget_factor is None:
        from .drivers import DRIVERS
        spec = DRIVERS.get(mplan.driver)
        budget_factor = spec.mem_budget_factor if spec is not None else 4.0
    if panel_checks is None:
        from .memory import PANEL_GATE_COPIES, panel_vmem_checks
        panel_checks = []
        op = mplan.driver.split("_")[0]
        n, nb = mplan.meta.get("n"), mplan.meta.get("nb")
        if op in PANEL_GATE_COPIES and n and nb:
            panel_checks = panel_vmem_checks(
                op, int(n), int(nb), mplan.meta.get("dtype", "float32"))
    findings = []
    findings += rule_mem_budget(mplan, budget_factor)
    findings += rule_vmem_overflow(panel_checks)
    findings += rule_missing_donation(mplan, closed_jaxpr)
    findings += rule_double_materialization(mplan, redist_log)
    return sorted(findings, key=lambda f: (f.rule, f.message))
