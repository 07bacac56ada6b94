"""Kind ``library_eig`` on ``el.Grid()`` 2x2 (four virtual CPU devices) at
N = 640 (over the driver's ``dc_min``, so the divide and conquer runs: two
replicated levels, the hand-off, one distributed merge): through the
harness from a throw-away copy, its answer against float64 numpy, a grid
that is not the configuration's refused, the cell's line holding every per-layer name ``BENCHMARK.json`` owes it, and the four
``*_wire_share`` readers (``benchmark/eig_wire.py``) on a hand-made trace:
they split ``redist_share`` by the eigensolve's stage."""
import json
import os

import jax
import numpy as np
import pytest

import bench_copy
import eig_wire
import reference
import run as harness
import scopes
import xplane
from test_scopes import entry_events

N = 640
CONFIG = {"kind": "library_eig", "operator": "herm_eig",
          "operand": "hpd_shifted", "n": N, "dtype": "float32", "nb": 64,
          "grid": [2, 2],
          "limits": {"residual": {"limit": 1e-6},
                     "orthogonality": {"limit": 1e-4},
                     "descents": {"limit": 0}}}
CELL = {"config": "t-heig-2x2", "traffic": "b2b.full", "chips": 4,
        "why": "test"}
SEED = 2147483999
WIRE = ("column_wire_share", "tridiag_wire_share", "dc_wire_share",
        "backtransform_wire_share")


@pytest.fixture
def bench_dir(tmp_path):
    dst = bench_copy.make(tmp_path / "benchmark")
    bench_copy.write_json(os.path.join(dst, "configs", "t-heig-2x2.json"),
                          CONFIG)
    bench_copy.write_json(os.path.join(dst, "workloads", "t.heig.2x2.json"),
                          CELL)
    return dst


def session_of(bench_dir, seed, devices=None):
    _cell, config, traffic = harness.resolve(bench_dir, "t.heig.2x2")
    kind = harness.load_module(bench_dir, "kinds", config["kind"])
    return kind.setup(config, traffic, devices or jax.devices()[:4], seed)


def operand(seed, i):
    key = np.uint32(reference.operand_key(seed, i, 0))
    return np.asarray(reference.plain_block(
        reference.ENTRIES["hpd_shifted"](N, key), 0, N, N), np.float64)


def test_run_is_correct_and_reports_every_end_to_end_metric(bench_dir):
    line = harness.main(["--workload", "t.heig.2x2", "--seed", str(SEED),
                         "--seconds", "0.2", "--trace", "0"],
                        bench_dir=bench_dir, devices=jax.devices()[:4])
    assert line["correct"] and line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["metrics"]) == {"solve_s", "plan_gb", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["count"] == 4


def test_answer_on_the_grid_matches_float64_numpy(bench_dir):
    """``(w, Z)`` of the timed path on 2x2 against ``numpy.linalg.eigh`` in
    float64 on the same generated A: eigenvalues to 50 eps ||A||_2, and the
    check's numbers (computed on the check's own mesh over the four
    devices) recomputed in float64 numpy within 5 %."""
    import elemental_tpu as el
    session = session_of(bench_dir, 7)
    assert session.facts["chips"] == 4 and session.facts["grid"] == [2, 2]
    w, Z = session.solve(session.prepare(3))
    assert len(Z.local.sharding.device_set) == 4
    got = session.check(3, (w, Z))
    w = np.asarray(w, np.float64)
    Zg = np.asarray(el.to_global(Z), np.float64)
    A = operand(7, 3)
    want = np.linalg.eigh(A)[0]
    eps = np.finfo(np.float32).eps
    assert np.abs(w - want).max() <= 50 * eps * np.abs(want).max()
    residual = np.linalg.norm(A @ Zg - Zg * w) / (
        np.linalg.norm(A) * np.linalg.norm(Zg))
    orthogonality = np.linalg.norm(Zg.T @ Zg - np.eye(N)) / np.sqrt(N)
    assert got["residual"] == pytest.approx(residual, rel=0.05)
    assert got["orthogonality"] == pytest.approx(orthogonality, rel=0.05)
    assert got["descents"] == 0.0


def test_a_grid_that_is_not_the_configurations_is_refused(bench_dir):
    """``el.Grid()`` makes 2x2 of four devices; a configuration that states
    1x4 is another deployment and is refused before anything compiles, as
    is the 2x2 configuration on one device."""
    _cell, config, traffic = harness.resolve(bench_dir, "t.heig.2x2")
    kind = harness.load_module(bench_dir, "kinds", config["kind"])
    with pytest.raises(ValueError, match="grid"):
        kind.setup({**config, "grid": [1, 4]}, traffic, jax.devices()[:4],
                   SEED)
    with pytest.raises(ValueError, match="grid"):
        kind.setup(config, traffic, jax.devices()[:1], SEED)


def test_the_cells_line_holds_every_name_it_owes(bench_dir, capsys):
    """Every file under ``layer_metrics/`` called on the facts of a real
    2x2 session, every op of the compiled program's entry given 10 ns: the
    line holds every per-layer metric of ``BENCHMARK.json`` that lists
    ``heig.2x2.b2b`` and every one that lists no cells (the driver's rule
    for a result's line), the four new readers among them."""
    harness.enable_cache()      # as main does: the set-up readers' log
    session = session_of(bench_dir, SEED)
    assert harness.judge([session.warm], CONFIG["limits"]) == 0
    name = session.facts["solve_module"]
    ops = entry_events(session._solve.as_text())
    window = [(f"{name}(1)", 1000.0, 10.0 * len(ops))]
    trace = xplane.reduce_trace(
        {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": window}}, name)
    run = {"facts": session.facts, "setup_s": 1.0,
           "peak": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}
    metrics = harness.read_metrics(bench_dir, "layer_metrics", trace, run)
    with open(os.path.join(os.path.dirname(bench_copy.BENCH),
                           "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    cell = "heig.2x2.b2b"
    owed = {m["name"] for m in per_layer if cell in m.get("workloads", [cell])}
    assert set(WIRE) <= owed
    assert owed <= set(metrics), sorted(owed - set(metrics))
    # the entry's exchanges are those made once a panel (the mirror, the
    # panels' gathers), the merges' and the back-transform's hops; of the
    # column loop's it holds only what the compiler hoisted out of the
    # ``while`` (the op keeps its name: what ran once reads as the column's)
    for reader in ("tridiag_wire_share", "dc_wire_share", "dc_share",
                   "backtransform_wire_share", "redist_share", "hemv_share"):
        assert metrics[reader]["value"] > 0.0, reader
    assert 0.0 <= metrics["column_wire_share"]["value"] \
        < metrics["tridiag_wire_share"]["value"] / 4
    split = sum(metrics[r]["value"] for r in WIRE[1:])
    assert split == pytest.approx(metrics["redist_share"]["value"])
    assert '"eig_wire"' in capsys.readouterr().out


def test_the_cell_is_the_one_chip_cells_deployment_on_2x2():
    """``heig.2x2.b2b`` is ``heig.1x1.b2b`` but for the grid: the same
    order, ``nb``, operand, traffic and limits, and ``guarantees`` word for
    word, so the pair is one problem on one chip and on four."""
    with open(os.path.join(os.path.dirname(bench_copy.BENCH),
                           "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [c for c in bench["workloads"] if c["name"] == "heig.2x2.b2b"]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "heig-full-2x2", "b2b.full", 4)
    _cell, grid, traffic = harness.resolve(bench_copy.BENCH, "heig.2x2.b2b")
    _cell, one, traffic_one = harness.resolve(bench_copy.BENCH,
                                              "heig.1x1.b2b")
    assert traffic == traffic_one
    assert grid["grid"] == [2, 2] and one["grid"] == [1, 1]
    for key in ("kind", "operator", "operand", "n", "dtype", "nb",
                "guarantees"):
        assert grid[key] == one[key], key
    assert (grid["n"], grid["nb"]) == (16384, 256)
    assert sorted(grid["reduced"]) == ["n"]
    assert {k: v["limit"] for k, v in grid["limits"].items()} == {
        k: v["limit"] for k, v in one["limits"].items()}


# ------------------------------------------------ the readers of the wire

P = "jit(bench_solve)/jit(main)/el.herm_eig/"
T = P + "el.hermitian_tridiag/"
L = T + "jit(_tridiag_panel)/while/body/closed_call/"
D = P + "el.tridiag_eig/jit(_tridiag_eig_jit)/"
R = "jit(_redistribute_jit)/shard_map/"

#: the grid eigensolve in miniature: the mirror once a panel, a column's
#: exchange (a collective, and a fusion that is named only by its root),
#: the product and the compiler's join of its partial sums, the update's
#: hop, a merge's SUMMA hop, the back-transform's gather, an exchange of
#: the generator under no stage, a compiler's copy
HLO = f"""HloModule jit_bench_solve, is_scheduled=true

%f (p: f32[8,8]) -> f32[8,8] {{
  %p = f32[8,8]{{1,0}} parameter(0)
  %neg.1 = f32[8,8]{{1,0}} negate(%p)
  ROOT %copy.1 = f32[8,8]{{1,0}} copy(%neg.1), metadata={{op_name="{L}k00/hemv/el.redist.MC_MR.to.STAR_STAR/{R}transpose"}}
}}

ENTRY %main.9 (A: f32[8,8]) -> f32[8,8] {{
  %A = f32[8,8]{{1,0}} parameter(0), metadata={{op_name="A.local"}}
  %all-to-all.1 = f32[8,8]{{1,0}} all-to-all(%A), metadata={{op_name="{T}k00/hemv/el.redist.MR_MC.to.MC_MR/{R}all_to_all"}}
  %all-gather.1 = f32[8,1]{{1,0}} all-gather(%A), metadata={{op_name="{L}k00/hemv/el.redist.MC_MR.to.MR_STAR/{R}all_gather"}}
  %fusion.1 = f32[8,8]{{1,0}} fusion(%A), kind=kLoop, calls=%f
  %fusion.2 = f32[8,1]{{1,0}} fusion(%A), kind=kLoop, calls=%g, metadata={{op_name="{L}k00/hemv/dot_general"}}
  %all-reduce.1 = f32[8,1]{{1,0}} all-reduce(%fusion.2), metadata={{op_name="{L}k00/hemv/dot_general"}}
  %all-gather.2 = f32[8,8]{{1,0}} all-gather(%A), metadata={{op_name="{T}k00/update/el.redist.STAR_STAR.to.MC_STAR/{R}all_gather"}}
  %dot.1 = f32[8,8]{{1,0}} dot(%A, %A), metadata={{op_name="{T}k00/update/dot_general"}}
  %all-gather.3 = f32[8,8]{{1,0}} all-gather(%A), metadata={{op_name="{D}k04/merge/el.gemm/k00/panel/el.redist.MC_MR.to.MC_STAR/{R}all_gather"}}
  %dot.2 = f32[8,8]{{1,0}} dot(%A, %A), metadata={{op_name="{D}k04/merge/el.gemm/k00/panel/dot_general"}}
  %all-gather.4 = f32[8,8]{{1,0}} all-gather(%A), metadata={{op_name="{P}el.apply_q_herm_tridiag/k31/apply/el.redist.MC_MR.to.STAR_STAR/{R}all_gather"}}
  %all-gather.5 = f32[8,8]{{1,0}} all-gather(%A), metadata={{op_name="jit(bench_solve)/jit(main)/el.redist.MC_MR.to.STAR_STAR/{R}all_gather"}}
  ROOT %copy.7 = f32[8,8]{{1,0}} copy(%dot.2)
}}
"""

#: instruction -> ns; 200 ns busy a solve
DURATIONS = {"all-to-all.1": 20, "all-gather.1": 30, "fusion.1": 10,
             "fusion.2": 50, "all-reduce.1": 12, "all-gather.2": 8,
             "dot.1": 20, "all-gather.3": 16, "dot.2": 14, "all-gather.4": 6,
             "all-gather.5": 4, "copy.7": 10}


def hand_made_trace(devices=2, solves=2):
    planes = {}
    for device in range(devices):
        ops, modules, t = [], [], 1000.0
        for _ in range(solves):
            start = t
            for name, dur in DURATIONS.items():
                ops.append((f"{name} f32[8,8]", t, float(dur)))
                t += dur
            modules.append(("jit_bench_solve(1)", start, t - start))
            t += 500.0
        planes[f"/device:TPU:{device}"] = {"XLA Ops": ops,
                                          "XLA Modules": modules}
    return xplane.reduce_trace(planes, "jit_bench_solve")


def run_of(operator="herm_eig", chips=4):
    return {"facts": {"operator": operator, "chips": chips,
                      "solve_module": "jit_bench_solve"},
            "peak": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 100e9}}


def readers(names=WIRE + ("redist_share", "hemv_share")):
    return {name: harness.load_module(bench_copy.BENCH, "layer_metrics",
                                      name) for name in names}


def test_wire_readers_split_redist_share_by_stage(monkeypatch, capsys):
    monkeypatch.setattr(scopes, "module_texts", lambda name: [HLO])
    trace, run = hand_made_trace(), run_of()
    got = {name: r.read(trace, run) for name, r in readers().items()}
    # the column loop's: the gather 30 and the fusion named by its root 10
    assert got["column_wire_share"] == pytest.approx(100 * 40 / 200)
    # with the mirror 20 and the update's hop 8
    assert got["tridiag_wire_share"] == pytest.approx(100 * 68 / 200)
    assert got["dc_wire_share"] == pytest.approx(100 * 16 / 200)
    assert got["backtransform_wire_share"] == pytest.approx(100 * 6 / 200)
    # the three stages sum to redist_share less what lies under no stage
    assert got["redist_share"] == pytest.approx(100 * 94 / 200)
    assert sum(got[r] for r in WIRE[1:]) == pytest.approx(
        got["redist_share"] - 100 * 4 / 200)
    # the compiler's join of the partial sums reads hemv, with the product
    assert got["hemv_share"] == pytest.approx(100 * 62 / 200)
    line = next(json.loads(text) for text in
                capsys.readouterr().out.splitlines() if '"eig_wire"' in text)
    assert line["seconds"]["-"] == pytest.approx(4e-9)
    assert line["by_name"][
        "hermitian_tridiag/column/el.redist.MC_MR.to.STAR_STAR"
    ] == pytest.approx(10e-9)
    assert line["by_name"]["tridiag_eig/el.redist.MC_MR.to.MC_STAR"] \
        == pytest.approx(16e-9)


def test_stage_of_reads_the_outermost_stage_and_the_loop():
    name = "el.redist.MC_MR.to.MR_STAR"
    hop = name + "/x"
    assert eig_wire.stage_of(L + "k03/hemv/" + hop) == (
        "hermitian_tridiag", True, name)
    assert eig_wire.stage_of(T + "k03/hemv/" + hop) == (
        "hermitian_tridiag", False, name)
    # a loop of the secular stage is no column loop
    assert eig_wire.stage_of(D + "k04/secular/while/body/" + hop) == (
        "tridiag_eig", False, name)
    assert eig_wire.stage_of("jit(f)/" + hop) == (None, False, name)
    # a loop AFTER the exchange's name is the engine's own
    assert eig_wire.stage_of(T + "k03/panel/el.redist.A.to.B/while/body/x"
                             ) == ("hermitian_tridiag", False,
                                   "el.redist.A.to.B")


def test_wire_readers_are_silent_elsewhere(monkeypatch):
    """One chip; another operator; a program that names nothing."""
    monkeypatch.setattr(scopes, "module_texts", lambda name: [HLO])
    trace = hand_made_trace()
    wire = readers(WIRE)
    assert all(r.read(trace, run_of(chips=1)) is None for r in wire.values())
    assert all(r.read(trace, run_of("hpd_solve")) is None
               for r in wire.values())
    bare = "\n".join(line.split(", metadata=")[0] for line in
                     HLO.split("\n"))
    monkeypatch.setattr(scopes, "module_texts", lambda name: [bare])
    trace = hand_made_trace()                       # a fresh cache entry
    assert all(r.read(trace, run_of()) is None for r in wire.values())
