"""Cost-model sanity: golden comm-plan agreement + schedule ranking.

The acceptance pins (ISSUE 4): with an empty cache the 'auto' knobs
resolve purely from the analytic cost model, and on 2x2 grids the model
ranks the lookahead+crossover schedules at or above classic -- CONSISTENT
with the golden comm plans' all_gather counts (the cost model's traced
collective counts at the golden geometry must equal the snapshots').
"""
import json
import math

import jax
import jax.numpy as jnp
import pytest

from elemental_tpu import Grid
from elemental_tpu.tune import TuneContext
from elemental_tpu.tune import cost_model as cm
from perf.comm_audit import golden_path

N, NB, XO = 64, 16, 32            # the golden comm-plan geometry


def _grid(r, c):
    return Grid(jax.devices()[: r * c], height=r)


def _score(op, la, xo, grid, nb=NB, n=N):
    ctx = TuneContext(op, (n, n), "float32", (grid.height, grid.width),
                      "cpu")
    return cm.score_config(op, {"nb": nb, "lookahead": la, "crossover": xo},
                           ctx=ctx, grid=grid, dtype=jnp.float32)


#: (op, schedule knobs) -> the golden snapshot each must agree with
_GOLDEN_VARIANTS = [
    ("cholesky", False, 0, "cholesky_classic"),
    ("cholesky", True, 0, "cholesky_lookahead"),
    ("cholesky", True, XO, "cholesky_crossover"),
    ("lu", False, 0, "lu_classic"),
    ("lu", True, 0, "lu_lookahead"),
    ("lu", True, XO, "lu_crossover"),
]


@pytest.mark.parametrize("op,la,xo,golden", _GOLDEN_VARIANTS,
                         ids=[g for *_, g in _GOLDEN_VARIANTS])
@pytest.mark.parametrize("grid_shape", [(1, 1), (2, 2)],
                         ids=["1x1", "2x2"])
def test_traced_counts_agree_with_golden(op, la, xo, golden, grid_shape):
    """The cost model's comm term comes from the same abstract traces the
    golden snapshots pin: per-collective counts must match exactly."""
    b = _score(op, la, xo, _grid(*grid_shape))
    with open(golden_path(golden, grid_shape)) as f:
        doc = json.load(f)
    expect = {prim: t["count"] for prim, t in doc["totals"].items()}
    assert b.prim_counts == expect, (b.prim_counts, expect)


@pytest.mark.parametrize("op", ["cholesky", "lu"])
def test_lookahead_crossover_ranks_at_or_above_classic_2x2(op):
    """THE acceptance pin: on a 2x2 grid the pipelined tail-crossover
    schedule scores <= classic at the golden geometry, for the same
    reason its golden plan has strictly fewer all_gathers."""
    g = _grid(2, 2)
    classic = _score(op, False, 0, g)
    xover = _score(op, True, XO, g)
    assert xover.prim_counts["all_gather"] < classic.prim_counts["all_gather"]
    assert xover.total_s <= classic.total_s, (
        xover.to_doc(), classic.to_doc())
    # and the comm terms alone agree with the ranking (flop term is equal)
    assert (xover.latency_s + xover.bandwidth_s
            <= classic.latency_s + classic.bandwidth_s)


@pytest.mark.parametrize("op", ["cholesky", "lu", "qr", "trsm", "herk",
                                "gemm"])
@pytest.mark.parametrize("grid_shape", [(1, 1), (2, 2)],
                         ids=["1x1", "2x2"])
def test_all_candidates_finite_positive(op, grid_shape):
    from elemental_tpu import tune
    g = _grid(*grid_shape)
    dims = (256, 256, 256) if op == "gemm" else (256, 256)
    _, scored = tune.explain(op, gshape=dims, dtype=jnp.float32, grid=g)
    assert scored, "no candidates"
    for b in scored:
        assert math.isfinite(b.total_s) and b.total_s > 0, b.to_doc()
        assert b.compute_s > 0
        assert b.latency_s >= 0 and b.bandwidth_s >= 0
    if grid_shape == (1, 1):
        # degenerate grid: no collectives at all
        assert all(b.rounds == 0 and b.comm_bytes == 0 for b in scored)


def test_large_problem_extrapolates_without_tracing_full_size():
    """n=32768 must score via the scaled trace geometry (bounded step
    count), with latency extrapolated to the real step count."""
    g = _grid(2, 2)
    b = _score("cholesky", True, 0, g, nb=2048, n=32768)
    assert max(b.detail["trace_dims"]) <= 128
    assert b.detail["lat_scale"] > 1
    # 16 real steps vs <= 6 traced: rounds extrapolate beyond the trace
    assert b.rounds > sum(b.prim_counts.values())


def test_gemm_closed_form_matches_traced_plan_shape():
    """The gemm closed form is calibrated against the abstract traces:
    at the golden geometry its all_gather ROUND COUNT for the stationary-C
    schedule matches the traced gemm_c plan (2 gathers per k-panel)."""
    from elemental_tpu import analysis as an
    g = _grid(2, 2)
    ctx = TuneContext("gemm", (N, N, N), "float32", (2, 2), "cpu")
    b = cm.score_config("gemm", {"alg": "C", "nb": NB}, ctx=ctx,
                        grid=g, dtype=jnp.float32)
    plan, _, _ = an.trace_driver("gemm_c", g, n=N, nb=NB)
    assert b.prim_counts.get("all_gather") == plan.count("all_gather")
    # and the ring-model byte estimate agrees to first order (same model)
    traced = sum(t["bytes"] for t in plan.totals().values())
    assert 0.5 <= b.comm_bytes / traced <= 2.0, (b.comm_bytes, traced)


def test_gemm_regime_selection():
    """The small-C / long-k regime on p > 1 must avoid the stationary
    panel sweeps (the SUMMA_NNDot rationale; the ring model ranks the
    one-shot 'gspmd' relayout of B cheapest, with 'dot' next); on 1x1
    grids dot leads by the zero-comm tie-break (the pinned
    one-local-matmul early-out)."""
    from elemental_tpu import tune
    g2 = _grid(2, 2)
    kn = tune.resolve_knobs("gemm", gshape=(32, 8192, 32),
                            dtype=jnp.float32, grid=g2,
                            knobs={"alg": "auto", "nb": None})
    assert kn["alg"] in ("dot", "gspmd")
    assert kn["nb"] is None                 # pinned default passes through
    g1 = _grid(1, 1)
    kn1 = tune.resolve_knobs("gemm", gshape=(256, 256, 256),
                             dtype=jnp.float32, grid=g1,
                             knobs={"alg": "auto", "nb": None})
    assert kn1["alg"] == "dot"


def test_crossover_default_matches_driver_constants():
    """The knob registry's literal DEFAULT_CROSSOVER must track the
    drivers' _CROSSOVER (they are deliberately not imported)."""
    from elemental_tpu.tune.knobs import DEFAULT_CROSSOVER
    from elemental_tpu.lapack.cholesky import _CROSSOVER as CHOL
    from elemental_tpu.lapack.lu import _CROSSOVER as LU
    assert DEFAULT_CROSSOVER == CHOL == LU


def test_unknown_backend_is_an_error_not_the_cpu_row():
    assert cm.machine_for("TPU") is cm.MACHINES["tpu"]
    with pytest.raises(ValueError, match="no machine model"):
        cm.machine_for("metal")


def test_engine_machine_terms_refuse_an_unknown_backend(monkeypatch):
    from elemental_tpu.redist import engine
    cpu = cm.machine_for("cpu")
    assert engine._machine_terms() == (cpu.latency_s, cpu.bw_bytes_per_s)
    monkeypatch.setattr(jax, "default_backend", lambda: "metal")
    with pytest.raises(ValueError, match="no machine model"):
        engine._machine_terms()
