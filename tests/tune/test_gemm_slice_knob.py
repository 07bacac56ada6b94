"""ISSUE 16: 'slice' in the gemm alg space -- cost-model ranking pins.

``alg='auto'`` must pick 'slice' exactly where its three single-collective
hops win (tall-skinny / non-square-grid geometry) and keep every existing
winner elsewhere: gspmd on square and long-k grids, the pinned dot
early-out on 1x1 (candidate-order tie-break, byte-identical)."""
import math

import jax
import jax.numpy as jnp

import elemental_tpu as el
from elemental_tpu import tune
from elemental_tpu.tune import TuneContext
from elemental_tpu.tune.knobs import (DOT_ELEMENT_CAP, GEMM_ALGS,
                                      _gemm_space)


def _grid(r, c):
    return el.Grid(jax.devices()[: r * c], height=r)


def _pick(gshape, grid, **extra):
    kn = tune.resolve_knobs("gemm", gshape=gshape, dtype=jnp.float32,
                            grid=grid,
                            knobs={"alg": "auto", "nb": None,
                                   "comm_precision": None,
                                   "redist_path": None, **extra})
    return kn["alg"]


def test_slice_registered_last():
    """'slice' appends at the END of GEMM_ALGS: every pre-existing exact
    tie keeps its historical winner, and 'dot' still leads the 1x1
    zero-comm tie-break."""
    assert GEMM_ALGS == ("dot", "C", "A", "B", "gspmd", "slice")


def test_auto_picks_slice_on_tall_skinny_2x4():
    assert _pick((8192, 512, 256), _grid(2, 4)) == "slice"


def test_auto_picks_slice_on_tall_skinny_2x2():
    assert _pick((8192, 512, 256), _grid(2, 2)) == "slice"


def test_auto_picks_slice_on_bench_headline_class():
    """A tall-skinny geometry of BASELINE.json's scale resolves 'slice'."""
    assert _pick((65536, 512, 512), _grid(2, 4)) == "slice"


def test_auto_keeps_dot_on_1x1():
    assert _pick((256, 256, 256), _grid(1, 1)) == "dot"
    assert _pick((8192, 512, 256), _grid(1, 1)) == "dot"


def test_auto_keeps_existing_winners_elsewhere():
    """Square and long-k geometry keep their pre-slice winners at full
    wire precision (slice ties gspmd byte-for-byte on squares; the
    candidate order breaks the tie the historical way)."""
    assert _pick((256, 256, 256), _grid(2, 2)) == "gspmd"
    assert _pick((4096, 4096, 4096), _grid(2, 2)) == "gspmd"
    assert _pick((32, 8192, 32), _grid(2, 2)) in ("dot", "gspmd")


def test_slice_priced_identically_across_redist_path():
    """The slicing route takes no ``redist_path`` (its hops are the
    engine's fused single-collective kernels, priced by the compiled
    plans' byte math): the knob's crossing must not change its score
    (deterministic resolution)."""
    from elemental_tpu.tune import cost_model as cm
    ctx = TuneContext("gemm", (8192, 512, 256), "float32", (2, 4), "cpu")
    scores = [cm.score_config("gemm", {"alg": "slice", "nb": None,
                                       "redist_path": rp},
                              ctx=ctx, grid=None, dtype=jnp.float32)
              for rp in (None, "direct")]
    assert scores[0].total_s == scores[1].total_s
    assert scores[0].comm_bytes == scores[1].comm_bytes


def test_slice_nb_collapsed():
    """nb is dead for the one-shot slice schedule: the space holds ONE
    slice candidate per (cp, rp) crossing, not one per nb rung."""
    ctx = TuneContext("gemm", (1024, 256, 128), "float32", (2, 2), "cpu")
    space = _gemm_space(ctx, {})
    slice_nbs = {c.get("nb") for c in space if c["alg"] == "slice"}
    assert len(slice_nbs) == 1
    c_nbs = {c.get("nb") for c in space if c["alg"] == "C"}
    assert len(c_nbs) > 1                  # the panel algs DO sweep nb


def test_slice_replicated_operand_memory_guard():
    """The mode rule replicates the small operand [STAR,STAR]; when even
    that exceeds the replication cap the candidate is skipped (same
    guard class as dot's replicated-C cap) -- unless explicitly pinned."""
    k = n = 1 << 12                        # k*n = 16M elems > cap
    m = 1 << 20
    assert k * n > DOT_ELEMENT_CAP
    ctx = TuneContext("gemm", (m, k, n), "float32", (2, 4), "cpu")
    assert not [c for c in _gemm_space(ctx, {}) if c["alg"] == "slice"]
    pinned = [c for c in _gemm_space(ctx, {"alg": "slice"})
              if c["alg"] == "slice"]
    assert pinned                          # explicit pin bypasses the guard
    # and within the cap the candidate exists
    ctx_ok = TuneContext("gemm", (m, 512, 512), "float32", (2, 4), "cpu")
    assert [c for c in _gemm_space(ctx_ok, {}) if c["alg"] == "slice"]


def test_slice_zero_comm_on_1x1_candidates():
    """Every slice candidate on a 1x1 grid scores zero rounds and zero
    comm bytes (the finite-positive invariant the shared tune test pins
    across the whole space)."""
    from elemental_tpu.tune import cost_model as cm
    ctx = TuneContext("gemm", (2048, 64, 16), "float32", (1, 1), "cpu")
    b = cm.score_config("gemm", {"alg": "slice", "nb": None}, ctx=ctx,
                        grid=None, dtype=jnp.float32)
    assert b.rounds == 0 and b.comm_bytes == 0
    assert math.isfinite(b.total_s) and b.compute_s > 0
