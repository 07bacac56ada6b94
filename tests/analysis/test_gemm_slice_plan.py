"""ISSUE 16 acceptance pins: the slicing gemm's comm plan.

At the tall-skinny golden geometry (the ``gemm_slice`` driver's
``(32n, n, n/4)`` extents) the slice schedule must run STRICTLY fewer
collective rounds than every SUMMA twin on both golden grids, and move
>= 1.5x fewer wire bytes than the stationary-C twin -- the honest
apples-to-apples baseline: stationary-C is the bit-identity reference of
the family and the only twin whose ABSTRACT TRACE carries its full wire
traffic (stationary-A/B and dot contract through GSPMD-inserted psums
that ``jax.make_jaxpr`` cannot see, so their traced bytes undercount;
the closed-form comparison below prices those psums and pins slice
cheapest against ALL five).
"""
import jax
import jax.numpy as jnp
import pytest

from elemental_tpu import Grid
from elemental_tpu import analysis as an
from elemental_tpu.analysis.drivers import (DEFAULT_N, DEFAULT_NB,
                                            _mcmr_input,
                                            gemm_slice_extents)
from elemental_tpu.core.distmatrix import DistMatrix
from elemental_tpu.core.dist import MC, MR, STAR
from elemental_tpu.redist.plan import gemm_slice_plans
from elemental_tpu.tune import TuneContext
from elemental_tpu.tune import cost_model as cm

M, K, N = gemm_slice_extents(DEFAULT_N)          # (2048, 64, 16)
TWINS = ("C", "A", "B", "dot", "gspmd")


def _grid(r, c):
    return Grid(jax.devices()[: r * c], height=r)


def _trace_alg(alg, grid, m=M, k=K, n=N):
    """Trace one gemm schedule at the tall-skinny geometry."""
    from elemental_tpu.blas.level3 import gemm

    def fn(a, b):
        A = DistMatrix(a, (m, k), MC, MR, 0, 0, grid)
        B = DistMatrix(b, (k, n), MC, MR, 0, 0, grid)
        return gemm(A, B, alg=alg, nb=DEFAULT_NB)
    args = (_mcmr_input(grid, m, k, jnp.float32),
            _mcmr_input(grid, k, n, jnp.float32))
    plan, _, _ = an.trace_callable(fn, args, name=f"gemm_{alg}", grid=grid)
    return plan


def _rounds_bytes(plan):
    tot = plan.totals()
    return (sum(t["count"] for t in tot.values()),
            sum(t["bytes"] for t in tot.values()))


def _psums(alg, grid_shape):
    """Closed-form psum count of one schedule (the contraction reductions
    GSPMD inserts at runtime -- INVISIBLE to the abstract trace, so the
    honest round count is traced hops + these)."""
    ctx = TuneContext("gemm", (M, K, N), "float32", grid_shape, "cpu")
    b = cm.score_config("gemm", {"alg": alg, "nb": DEFAULT_NB}, ctx=ctx,
                        grid=None, dtype=jnp.float32)
    return b.prim_counts.get("psum", 0)


@pytest.mark.parametrize("grid_shape", [(2, 2), (2, 4)],
                         ids=["2x2", "2x4"])
def test_slice_strictly_fewer_rounds_than_every_twin(grid_shape):
    g = _grid(*grid_shape)
    s_rounds, _ = _rounds_bytes(_trace_alg("slice", g))
    assert s_rounds == 3                    # one collective a hop
    assert _psums("slice", grid_shape) == 0  # k unsharded: NO hidden psum
    for alg in TWINS:
        t_rounds, _ = _rounds_bytes(_trace_alg(alg, g))
        t_rounds += _psums(alg, grid_shape)
        assert s_rounds < t_rounds, (alg, s_rounds, t_rounds)


@pytest.mark.parametrize("grid_shape", [(2, 2), (2, 4)],
                         ids=["2x2", "2x4"])
def test_slice_1p5x_fewer_wire_bytes_than_stationary_c(grid_shape):
    """>= 1.5x vs the stationary-C twin on both golden grids, traced.
    (Stationary-A/B/dot traced bytes omit their invisible GSPMD psums --
    the closed-form pin below covers those honestly.)"""
    g = _grid(*grid_shape)
    _, s_bytes = _rounds_bytes(_trace_alg("slice", g))
    _, c_bytes = _rounds_bytes(_trace_alg("C", g))
    assert c_bytes >= 1.5 * s_bytes, (s_bytes, c_bytes)


def test_slice_closed_form_beats_every_twin_on_2x4():
    """Psums priced in (the ring model's 2B(S-1)/S), slice still moves
    >= 1.5x fewer comm bytes than the BEST twin on the non-square grid."""
    ctx = TuneContext("gemm", (M, K, N), "float32", (2, 4), "cpu")
    def score(alg):
        return cm.score_config("gemm", {"alg": alg, "nb": DEFAULT_NB},
                               ctx=ctx, grid=None, dtype=jnp.float32)
    s = score("slice")
    best_twin = min(score(a).comm_bytes for a in TWINS)
    assert best_twin >= 1.5 * s.comm_bytes, (s.comm_bytes, best_twin)
    # the closed form collapses each twin's multi-hop operand chain to
    # one gather, so rounds there are a LOWER bound; slice still never
    # exceeds any twin, and the traced pin above is strict.
    assert all(s.rounds <= score(a).rounds for a in TWINS)


@pytest.mark.parametrize("extents,mode", [((M, K, N), "rows"),
                                          ((37, 23, 11), "rows"),
                                          ((11, 23, 37), "cols")],
                         ids=["golden", "ragged_rows", "ragged_cols"])
@pytest.mark.parametrize("grid_shape", [(2, 2), (2, 4)],
                         ids=["2x2", "2x4"])
def test_traced_bytes_equal_compiled_plan_bytes(grid_shape, extents, mode):
    """What the tuner prices is what the executor ships (no hidden psum
    on the slice path).  Since ISSUE 51 the three hops run the engine's
    fused kernels: ONE all-gather over the whole grid (the small operand
    to [STAR,STAR]) and TWO all-to-alls over one mesh axis (the slice in,
    the product out) -- and they ship the wire bytes of the compiled
    plans of the same three pairs to the byte, ragged extents and their
    padding included, so ``tune.cost_model`` keeps the plans' byte math
    (``chain_cost``'s closed form leaves the ragged padding out)."""
    g = _grid(*grid_shape)
    m, k, n = extents
    totals = _trace_alg("slice", g, m, k, n).totals()
    assert {p: t["count"] for p, t in totals.items()} == {
        "all_gather": 1, "all_to_all": 2}
    got_mode, plans = gemm_slice_plans(m, k, n, grid_shape)
    assert got_mode == mode
    star = [p.wire_bytes(4) for _, p in plans if p.dst == (STAR, STAR)]
    rest = [p.wire_bytes(4) for _, p in plans if p.dst != (STAR, STAR)]
    assert totals["all_gather"]["bytes"] == sum(star) and len(star) == 1
    assert totals["all_to_all"]["bytes"] == sum(rest) and len(rest) == 2
    # and the cost model labels its sites with the collectives that run
    ctx = TuneContext("gemm", extents, "float32", grid_shape, "cpu")
    b = cm.score_config("gemm", {"alg": "slice", "nb": None}, ctx=ctx,
                        grid=None, dtype=jnp.float32)
    assert b.prim_counts == {"all_gather": 1, "all_to_all": 2}
    assert b.comm_bytes == sum(star) + sum(rest)


@pytest.mark.parametrize("grid_shape,mode", [((4, 1), "rows"),
                                             ((1, 4), "cols"),
                                             ((1, 8), "cols"),
                                             ((8, 1), "rows")],
                         ids=["4x1", "1x4", "1x8", "8x1"])
def test_degenerate_grids_single_collective(grid_shape, mode):
    """Nx1 / 1xN: two of the three legs are pure local relabelings, so
    the whole gemm is ONE collective (the small-operand broadcast)."""
    g = _grid(*grid_shape)
    rounds, _ = _rounds_bytes(_trace_alg("slice", g))
    assert rounds == 1
    got_mode, plans = gemm_slice_plans(M, K, N, grid_shape)
    assert got_mode == mode
    assert sum(p.rounds for _, p in plans if p is not None) == 1


def test_slice_golden_matches_live_trace():
    """The checked-in golden is the live trace (check.sh gate mirror)."""
    import json
    from perf.comm_audit import golden_path
    plan = _trace_alg("slice", _grid(2, 2))
    with open(golden_path("gemm_slice", (2, 2))) as f:
        doc = json.load(f)
    assert {p: t["count"] for p, t in doc["totals"].items()} == \
        {p: t["count"] for p, t in plan.totals().items()}
    assert {p: t["bytes"] for p, t in doc["totals"].items()} == \
        {p: t["bytes"] for p, t in plan.totals().items()}
