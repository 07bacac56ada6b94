"""Golden memory-plan gates (ISSUE 18): every registered driver's
per-device peak live bytes, high-water attribution and replicated-
materialization census pinned at the jaxpr level on 1x1 and 2x2 grids.

Trace-only like the comm-plan twins: a PR that silently doubles a
driver's resident footprint (an extra gathered slab, a new replicated
form, a dropped buffer reuse) fails here instead of OOMing on hardware.
Regenerate after an INTENTIONAL change with
``python -m perf.comm_audit mem-diff --update-golden``.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elemental_tpu import Grid
from elemental_tpu import analysis as an
from perf.comm_audit import GRIDS, mem_golden_path


def _grid(r, c):
    return Grid(jax.devices()[: r * c], height=r)


_CASES = [(d, g) for d in an.driver_names() for g in GRIDS]


@pytest.mark.parametrize("driver,grid", _CASES,
                         ids=[f"{d}-{r}x{c}" for d, (r, c) in _CASES])
def test_memory_plan_matches_golden(driver, grid):
    mplan, _, _ = an.trace_memory(driver, _grid(*grid))
    path = mem_golden_path(driver, grid)
    with open(path) as f:
        golden = json.load(f)
    lines = an.diff_mem_docs(golden, an.golden_mem_doc(mplan))
    assert not lines, "memory plan drifted from golden " \
        f"({path}):\n" + "\n".join(lines) + \
        "\nIf intentional: python -m perf.comm_audit mem-diff " \
        "--update-golden"


def test_diff_detects_seeded_drift():
    """mem-diff must FAIL on drift, not just pass on agreement: a seeded
    peak/census/timeline perturbation each produces a mismatch line."""
    mplan, _, _ = an.trace_memory("gemm_a", _grid(2, 2))
    doc = an.golden_mem_doc(mplan)
    assert an.diff_mem_docs(doc, doc) == []
    drifted = json.loads(json.dumps(doc))
    drifted["peak_bytes"] += 4096
    assert any("peak_bytes" in ln for ln in an.diff_mem_docs(doc, drifted))
    drifted = json.loads(json.dumps(doc))
    drifted["replicated"]["count"] += 1
    assert any("replicated" in ln for ln in an.diff_mem_docs(doc, drifted))
    drifted = json.loads(json.dumps(doc))
    drifted["timeline"] = drifted["timeline"][:-1]
    assert any("timeline" in ln for ln in an.diff_mem_docs(doc, drifted))


# ---------------------------------------------------------------------
# liveness-walk unit behavior
# ---------------------------------------------------------------------

def test_walk_counts_args_and_peak():
    """A chain that frees its intermediate peaks below sum-of-all."""
    def chain(x):
        y = x * 2.0          # x, y live
        z = y + 1.0          # y frees after this
        return z * z

    closed = jax.make_jaxpr(chain)(
        jax.ShapeDtypeStruct((64, 64), jnp.float32))
    stats = an.analyze_jaxpr(closed)
    one = 64 * 64 * 4
    assert stats.args_bytes == one
    assert stats.outs_bytes == one
    # x + y + z live at the z allocation, never all four values at once
    assert stats.peak_bytes == 3 * one
    assert stats.static
    assert stats.timeline[-1].live_bytes == stats.peak_bytes


def test_walk_fanout_holds_operand():
    """An operand consumed twice stays live until its LAST use."""
    def fan(x):
        y = x * 2.0
        z = y + x            # x's last use
        return z - 1.0

    closed = jax.make_jaxpr(fan)(
        jax.ShapeDtypeStruct((32, 32), jnp.float32))
    one = 32 * 32 * 4
    assert an.analyze_jaxpr(closed).peak_bytes == 3 * one


def test_walk_sees_through_an_optimization_barrier():
    """The barrier's result IS its operand's buffer: it allocates nothing,
    and a use behind it keeps the operand alive (the engine's de-interleave
    takes every operand behind one, ISSUE 32)."""
    def plain(x):
        y = x * 2.0
        return y.reshape(16, 64) + 1.0

    def barred(x):
        y = jax.lax.optimization_barrier(x * 2.0)
        return y.reshape(16, 64) + 1.0

    x = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    want = an.analyze_jaxpr(jax.make_jaxpr(plain)(x))
    got = an.analyze_jaxpr(jax.make_jaxpr(barred)(x))
    assert got.peak_bytes == want.peak_bytes == 3 * 32 * 32 * 4
    assert [m.live_bytes for m in got.timeline] == [
        m.live_bytes for m in want.timeline]


def test_walk_divides_by_grid_size():
    def f(x):
        return x * 2.0

    closed = jax.make_jaxpr(f)(
        jax.ShapeDtypeStruct((64, 64), jnp.float32))
    s1 = an.analyze_jaxpr(closed, grid_size=1)
    s4 = an.analyze_jaxpr(closed, grid_size=4)
    assert s1.peak_bytes == 4 * s4.peak_bytes


def test_walk_scan_body_once():
    """A scan body is steady-state: its footprint counts once, not
    length times (buffers free between iterations)."""
    def f(x):
        def body(c, _):
            return c * 2.0 + 1.0, None
        out, _ = jax.lax.scan(body, x, None, length=8)
        return out

    closed = jax.make_jaxpr(f)(
        jax.ShapeDtypeStruct((16, 16), jnp.float32))
    stats = an.analyze_jaxpr(closed)
    one = 16 * 16 * 4
    assert stats.peak_bytes < 8 * one


def test_walk_cond_branches_max_not_sum():
    def f(x):
        return jax.lax.cond(x.sum() > 0.0,
                            lambda v: v * 2.0 + 1.0,
                            lambda v: v - 1.0, x)

    closed = jax.make_jaxpr(f)(
        jax.ShapeDtypeStruct((16, 16), jnp.float32))
    stats = an.analyze_jaxpr(closed)
    one = 16 * 16 * 4
    # x + the busier branch's two intermediates, NOT both branches at once
    assert stats.peak_bytes <= 3 * one + 8


def test_peak_attribution_names_scope():
    mplan, _, _ = an.trace_memory("gemm_slice", _grid(2, 2))
    doc = mplan.to_doc()
    assert doc["peak_path"], "peak must be attributed to a nesting path"
    assert doc["peak_prim"]
    assert doc["timeline"], "high-water timeline must be non-empty"
    marks = [t["live_bytes"] for t in doc["timeline"]]
    assert marks == sorted(marks), "timeline marks are monotone peaks"
    assert marks[-1] == doc["walk_peak_bytes"]


# ---------------------------------------------------------------------
# replicated-materialization census
# ---------------------------------------------------------------------

def test_census_star_star_replication():
    """A [*,*] gather on 2x2 keeps p=4 replicas: extra = 3/4 of the
    operand per device, and star_star counts it."""
    mplan, _, log = an.trace_memory("gemm_slice", _grid(2, 2))
    rep = mplan.replicated
    assert rep["star_star"] >= 1
    star = [s for s in rep["sites"] if s["dst"] == "[STAR,STAR]"]
    assert star
    m, n = star[0]["gshape"]
    z = np.dtype(star[0]["dtype"]).itemsize
    assert star[0]["extra_bytes"] == m * n * z * 3 // 4 * star[0]["count"]
    assert mplan.peak_bytes == mplan.stats.peak_bytes \
        + rep["max_extra_bytes"]


def test_census_empty_on_1x1():
    """No replication exists on one device: census must be silent."""
    for driver in ("gemm_a", "cholesky_classic", "lu_classic"):
        mplan, _, _ = an.trace_memory(driver, _grid(1, 1))
        assert mplan.replicated["count"] == 0
        assert mplan.replicated["max_extra_bytes"] == 0


def test_census_panel_spread_counts_both_forms():
    """panel_spread produces BOTH panel forms from one entry; each
    replicated form contributes extra bytes."""
    mplan, _, log = an.trace_memory("cholesky_classic", _grid(2, 2))
    spreads = [r for r in log if r.kind == "panel_spread"]
    assert spreads, "cholesky's trailing update uses panel_spread"
    assert mplan.replicated["count"] >= 2 * len(spreads)


# ---------------------------------------------------------------------
# EL007 support: the VMEM gate cross-check helpers
# ---------------------------------------------------------------------

def test_gate_bytes_reproduce_use_pallas():
    """check_panel_vmem's `admitted` IS the PanelPlan gate's decision
    at the default budget, for every op and a spread of shapes."""
    from elemental_tpu.kernels import PanelPlan
    plan = PanelPlan(impl="pallas", inners=(512, 64), source="test")
    for op, copies in an.PANEL_GATE_COPIES.items():
        for shape in ((64, 16), (512, 128), (2048, 512), (8192, 1024)):
            chk = an.check_panel_vmem(op, shape, "float32")
            assert chk.admitted == plan.use_pallas(shape, jnp.float32,
                                                   copies=copies), \
                (op, shape)


def test_kernel_bytes_exceed_gate_for_cholesky_odd_width():
    """The genuine gate/kernel divergence EL007 exists to catch: potrf's
    kernel LANE-pads BOTH axes, so non-128-multiple widths allocate
    MORE than the (8,128) tile pricing admits."""
    chk = an.check_panel_vmem("cholesky", (72, 72), "float32")
    assert chk.kernel_bytes > chk.gate_bytes
    # at the default 16 MiB budget the slack absorbs it: no overflow
    assert chk.admitted and chk.fits and not chk.overflow


def test_panel_shapes_enumerate_sweep():
    shapes = an.panel_shapes("lu", 64, 16)
    assert shapes == [(64, 16), (48, 16), (32, 16), (16, 16)]
    assert an.panel_shapes("cholesky", 64, 16) == [(16, 16)] * 4
    # ragged tail
    assert an.panel_shapes("qr", 40, 16) == [(40, 16), (24, 16), (8, 8)]
