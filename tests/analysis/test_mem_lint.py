"""Memory lint rules EL006-EL009 (ISSUE 18).

Each rule is exercised positively (a seeded violation fires) and
negatively (the registry is clean / the guard conditions hold).  EL007
additionally pins GATE AGREEMENT: the static cross-check and the dynamic
``use_pallas`` gate must reach the same verdict on the same oversized
panel -- the lint is only trustworthy if it models the gate exactly.
"""
import jax
import jax.numpy as jnp
import pytest

from elemental_tpu import Grid
from elemental_tpu import analysis as an
from elemental_tpu.analysis.lint import (rule_mem_budget,
                                         rule_vmem_overflow,
                                         rule_missing_donation,
                                         rule_double_materialization)
from elemental_tpu.kernels import PanelPlan
from elemental_tpu.kernels.common import PANEL_VMEM_BUDGET


def _grid(r, c):
    return Grid(jax.devices()[: r * c], height=r)


# ---------------------------------------------------------------------
# EL006 peak-over-budget
# ---------------------------------------------------------------------

def test_el006_fires_on_tight_budget():
    mplan, closed, log = an.trace_memory("gemm_slice", _grid(2, 2))
    findings = an.lint_memory(mplan, log, closed, budget_factor=1.0)
    el6 = [f for f in findings if f.rule == "EL006"]
    assert len(el6) == 1
    assert "exceeds the declared budget" in el6[0].message
    assert "MEM_BUDGET_FACTORS" in el6[0].fix_hint


def test_el006_quiet_at_declared_budget():
    mplan, closed, log = an.trace_memory("gemm_slice", _grid(2, 2))
    assert an.lint_memory(mplan, log, closed) == []


def test_el006_names_high_water_scope():
    mplan, _, _ = an.trace_memory("gemm_slice", _grid(2, 2))
    (f,) = rule_mem_budget(mplan, 1.0)
    assert "high-water at" in f.message
    assert mplan.stats.peak_prim in f.message


def test_declared_factors_cover_both_grids():
    """Every override in MEM_BUDGET_FACTORS is load-bearing AND
    sufficient: the driver exceeds the 4.0 default on some grid and
    fits its declared factor on all."""
    for name, factor in an.MEM_BUDGET_FACTORS.items():
        ratios = []
        for grid in ((1, 1), (2, 2)):
            mplan, _, _ = an.trace_memory(name, _grid(*grid))
            base = mplan.stats.args_bytes + mplan.stats.outs_bytes
            ratios.append(mplan.peak_bytes / max(base, 1))
            assert rule_mem_budget(mplan, factor) == [], (name, grid)
        assert max(ratios) > 4.0, \
            f"{name}: override {factor} no longer needed (max ratio " \
            f"{max(ratios):.2f}) -- delete it from MEM_BUDGET_FACTORS"


# ---------------------------------------------------------------------
# EL007 vmem-overflow + dynamic-gate agreement
# ---------------------------------------------------------------------

#: a panel the 16 MiB gate ADMITS (4 tile-padded f32 copies of
#: 1024x1024 = 16 MiB) but whose compiled qr kernel allocates about
#: 30 MiB: under the compiler's DEFAULT 16 MiB scoped limit -- what the
#: kernels were dispatched with before they set vmem_limit_bytes -- it
#: is refused, the exact divergence class EL007 exists to catch
_OVERSIZED = ("qr", (1024, 1024), "float32")
_DEFAULT_GRANT = 16 * 2 ** 20


def test_el007_fires_on_oversized_panel():
    op, shape, dtype = _OVERSIZED
    # with the limit the kernels ask for, the gate's corner fits ...
    assert not an.check_panel_vmem(op, shape, dtype).overflow
    # ... with the compiler's own default it does not
    chk = an.check_panel_vmem(op, shape, dtype, limit=_DEFAULT_GRANT)
    assert chk.admitted and not chk.fits and chk.overflow
    (f,) = rule_vmem_overflow([chk])
    assert f.rule == "EL007" and f.severity == "error"
    assert str(chk.kernel_bytes) in f.message


def test_el007_dynamic_gate_agrees_on_oversized_panel():
    """The dynamic gate verdict for the seeded EL007 panel: use_pallas
    ADMITS it (that is the bug class -- the kernel would overflow), and
    pricing at the compiled kernel's honest footprint makes the SAME
    gate refuse it."""
    op, shape, _ = _OVERSIZED
    gate_copies = an.PANEL_GATE_COPIES[op]
    plan = PanelPlan(impl="pallas", inners=(512, 64), source="test")
    chk = an.check_panel_vmem(op, shape, "float32")
    # the dynamic gate at the dispatch site's copies ADMITS the panel --
    # same verdict as the static check (that IS the bug class)
    assert plan.use_pallas(shape, jnp.float32, copies=gate_copies)
    assert chk.admitted
    # priced at the kernel's honest resident count, the SAME dynamic
    # gate refuses it -- the fix EL007's hint prescribes
    per_copy = chk.gate_bytes // gate_copies
    honest = -(-chk.kernel_bytes // per_copy)
    assert honest > gate_copies
    assert not plan.use_pallas(shape, jnp.float32, copies=honest)


def test_el007_quiet_on_default_sweeps():
    """Every panel shape the registered drivers actually dispatch at
    their default geometry passes the cross-check (goldens stay clean)."""
    for op in an.PANEL_GATE_COPIES:
        for chk in an.panel_vmem_checks(op, an.DEFAULT_N, an.DEFAULT_NB):
            assert not chk.overflow, chk


def test_el007_not_admitted_is_not_overflow():
    """A panel the gate already REJECTS is the fallback working as
    designed, not a finding."""
    chk = an.check_panel_vmem("cholesky", (4096, 4096), "float64",
                              budget=PANEL_VMEM_BUDGET)
    assert not chk.admitted and not chk.fits
    assert rule_vmem_overflow([chk]) == []


# ---------------------------------------------------------------------
# EL008 missing-donation
# ---------------------------------------------------------------------

def _aba_plan(donated):
    """A jit-style entry whose output aval equals input 0's aval."""
    def fn(a, b):
        return (a * 2.0 + b).astype(a.dtype)

    args = (jax.ShapeDtypeStruct((32, 32), jnp.float32),
            jax.ShapeDtypeStruct((32, 32), jnp.float32))
    closed = jax.make_jaxpr(fn)(*args)
    meta = {"n": 32, "dtype": "float32"}
    if donated is not None:
        meta["donated"] = donated
    mplan = an.memory_plan("toy_entry", (1, 1), meta, closed)
    return mplan, closed


def test_el008_fires_on_undonated_matching_input():
    mplan, closed = _aba_plan(donated=())
    findings = rule_missing_donation(mplan, closed)
    # BOTH f32 (32,32) inputs match the output aval and neither is donated
    assert [f.rule for f in findings] == ["EL008", "EL008"]
    assert "donate_argnums" in findings[0].fix_hint


def test_el008_quiet_when_donated():
    mplan, closed = _aba_plan(donated=(0, 1))
    assert rule_missing_donation(mplan, closed) == []


def test_el008_skips_undeclared_entries():
    """No meta['donated'] = the entry never claimed donation semantics;
    the registry drivers stay out of scope (and lint clean)."""
    mplan, closed = _aba_plan(donated=None)
    assert rule_missing_donation(mplan, closed) == []


def test_el008_serve_executor_paths_lintable():
    """The serve exec-cache kernels, linted through the same rule: the
    donated build is clean, the undonated build of the same kernel has
    findings -- the `__donated` convention is now checkable."""
    from elemental_tpu.serve.executor import _kernel

    args = (jax.ShapeDtypeStruct((4, 16, 16), jnp.float64),
            jax.ShapeDtypeStruct((4, 16, 2), jnp.float64))
    closed = jax.make_jaxpr(jax.vmap(_kernel("hpd")))(*args)
    meta = {"n": 16, "dtype": "float64"}
    donated = an.memory_plan("serve_hpd", (1, 1),
                             dict(meta, donated=(0, 1)), closed)
    undonated = an.memory_plan("serve_hpd", (1, 1),
                               dict(meta, donated=()), closed)
    assert rule_missing_donation(donated, closed) == []
    assert any(f.rule == "EL008"
               for f in rule_missing_donation(undonated, closed))


# ---------------------------------------------------------------------
# EL009 double-materialization
# ---------------------------------------------------------------------

def test_el009_fires_on_repeated_full_gather():
    """Two [*,*] gathers of the same DistMatrix = p replicas paid twice."""
    from elemental_tpu.core.dist import Dist
    from elemental_tpu.redist.engine import redistribute, redist_trace
    import elemental_tpu as el

    g = _grid(2, 2)
    STAR = Dist.STAR

    def fn(a):
        A = el.DistMatrix(a, (16, 16), Dist.MC, Dist.MR, 0, 0, g)
        F1 = redistribute(A, STAR, STAR)
        F2 = redistribute(A, STAR, STAR)
        return F1.local + F2.local

    from elemental_tpu.analysis.drivers import storage_shape
    arg = jax.ShapeDtypeStruct(
        storage_shape(16, 16, Dist.MC, Dist.MR, g), jnp.float32)
    with redist_trace() as log:
        closed = jax.make_jaxpr(fn)(arg)
    mplan = an.memory_plan("toy_double", (2, 2), {"n": 16}, closed, log)
    findings = rule_double_materialization(mplan, log)
    assert [f.rule for f in findings] == ["EL009"]
    assert "2 separate [*,*] gathers" in findings[0].message
    assert "hoist" in findings[0].fix_hint


def test_el009_quiet_on_distinct_operands():
    from elemental_tpu.core.dist import Dist
    from elemental_tpu.redist.engine import redistribute, redist_trace
    from elemental_tpu.analysis.drivers import storage_shape
    import elemental_tpu as el

    g = _grid(2, 2)

    def fn(a, b):
        A = el.DistMatrix(a, (16, 16), Dist.MC, Dist.MR, 0, 0, g)
        B = el.DistMatrix(b, (16, 16), Dist.MC, Dist.MR, 0, 0, g)
        FA = redistribute(A, Dist.STAR, Dist.STAR)
        FB = redistribute(B, Dist.STAR, Dist.STAR)
        return FA.local + FB.local

    arg = jax.ShapeDtypeStruct(
        storage_shape(16, 16, Dist.MC, Dist.MR, g), jnp.float32)
    with redist_trace() as log:
        closed = jax.make_jaxpr(fn)(arg, arg)
    mplan = an.memory_plan("toy_two", (2, 2), {"n": 16}, closed, log)
    assert rule_double_materialization(mplan, log) == []


# ---------------------------------------------------------------------
# the registry stays clean end to end
# ---------------------------------------------------------------------

@pytest.mark.parametrize("grid", [(1, 1), (2, 2)], ids=["1x1", "2x2"])
def test_registry_lints_clean(grid):
    for driver in an.driver_names():
        mplan, closed, log = an.trace_memory(driver, _grid(*grid))
        findings = an.lint_memory(mplan, log, closed)
        assert findings == [], \
            f"{driver} {grid}: " + "; ".join(str(f) for f in findings)
