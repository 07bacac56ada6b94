"""The two readers of the distributed pivoted LU (``row_permute_share``,
``panel_gather_share``): their arithmetic on a hand-made trace of two
devices, where they stay silent, and on a CPU-compiled N = 256 twin of
``lu16k.2x2.b2b`` through the harness's own loader."""
import json
import os

import jax
import pytest

import bench_copy
import run as harness
import scopes
import xplane
from test_scopes import entry_events

P = "jit(bench_solve)/jit(main)/el.lu_solve/"

#: the distributed LU in miniature: a panel gather (collective and its
#: unpack), the replicated ladder, a swap (gather, the partitioner's
#: all-reduce under the gather's name, scatter), an update, the
#: permutation of B in the sweeps, a compiler's copy
HLO = f"""HloModule jit_bench_solve, is_scheduled=true

ENTRY %main.9 (A: f32[8,8]) -> f32[8,8] {{
  %A = f32[8,8]{{1,0}} parameter(0), metadata={{op_name="A.local"}}
  %all-gather.1 = f32[8,8]{{1,0}} all-gather(%A), metadata={{op_name="{P}factor/el.lu/k00/panel/el.redist.MC_MR.to.STAR_STAR/shmap/all_gather"}}
  %fusion.1 = f32[8,8]{{1,0}} fusion(%all-gather.1), kind=kLoop, calls=%f, metadata={{op_name="{P}factor/el.lu/k00/panel/el.redist.MC_MR.to.STAR_STAR/reshape"}}
  %while.1 = f32[8,8]{{1,0}} while(%fusion.1), condition=%c, body=%b, metadata={{op_name="{P}factor/el.lu/k00/panel/while"}}
  %gather.1 = f32[8,8]{{1,0}} gather(%A, %A), metadata={{op_name="{P}factor/el.lu/k00/swap/el.redist.row_permute/jit(_take)/gather"}}
  %all-reduce.1 = f32[8,8]{{1,0}} all-reduce(%gather.1), metadata={{op_name="{P}factor/el.lu/k00/swap/el.redist.row_permute/jit(_take)/gather"}}
  %scatter.1 = f32[8,8]{{1,0}} scatter(%A, %A, %all-reduce.1), metadata={{op_name="{P}factor/el.lu/k00/swap/el.redist.row_permute/scatter"}}
  %dot.1 = f32[8,8]{{1,0}} dot(%A, %A), metadata={{op_name="{P}factor/el.lu/k00/update/dot_general"}}
  %gather.2 = f32[8,8]{{1,0}} gather(%A, %A), metadata={{op_name="{P}sweeps/el.redist.row_permute/jit(_take)/gather"}}
  ROOT %copy.7 = f32[8,8]{{1,0}} copy(%dot.1)
}}
"""

#: (instruction, duration in ns) on each of the two devices: 100 ns busy
DURATIONS = {
    0: {"all-gather.1": 6, "fusion.1": 4, "while.1": 50, "gather.1": 5,
        "all-reduce.1": 8, "scatter.1": 5, "dot.1": 18, "gather.2": 2,
        "copy.7": 2},
    1: {"all-gather.1": 10, "fusion.1": 4, "while.1": 50, "gather.1": 5,
        "all-reduce.1": 4, "scatter.1": 5, "dot.1": 18, "gather.2": 2,
        "copy.7": 2},
}


def hand_made_trace(solves=2):
    planes = {}
    for device, durations in DURATIONS.items():
        ops, modules, t = [], [], 1000.0
        for _ in range(solves):
            start = t
            for name, dur in durations.items():
                ops.append((f"{name} f32[8,8]", t, float(dur)))
                t += dur
            modules.append(("jit_bench_solve(1)", start, t - start))
            t += 500.0                              # the host between solves
        planes[f"/device:TPU:{device}"] = {"XLA Ops": ops,
                                           "XLA Modules": modules}
    return xplane.reduce_trace(planes, "jit_bench_solve")


def facts(operator="lu_solve", chips=4):
    return {"facts": {"operator": operator, "chips": chips,
                      "solve_module": "jit_bench_solve"}}


def readers():
    return {name: harness.load_module(bench_copy.BENCH, "layer_metrics",
                                      name)
            for name in ("row_permute_share", "panel_gather_share")}


def test_shares_on_a_hand_made_trace(monkeypatch, capsys):
    monkeypatch.setattr(scopes, "module_texts", lambda name: [HLO])
    trace, run = hand_made_trace(), facts()
    got = {name: r.read(trace, run) for name, r in readers().items()}
    # row_permute: (5 + 8 + 5 + 2) and (5 + 4 + 5 + 2) of 100 ns busy;
    # the panel gather: (6 + 4) and (10 + 4); means over the two devices
    assert got["row_permute_share"] == pytest.approx(18.0)
    assert got["panel_gather_share"] == pytest.approx(12.0)
    summary = scopes.summary(trace, run)
    assert summary["seconds"]["el.redist.row_permute"] == \
        pytest.approx(18e-9)                 # a solve, not the window's two
    # both are parts of redist_share, and the classes still sum to 100
    assert summary["share"]["redist"] == pytest.approx(30.0)
    assert summary["sum"] == pytest.approx(100.0)
    assert "el.redist.row_permute" in capsys.readouterr().out


def test_list_trace_ranks_ops_with_their_scopes():
    import list_trace
    got = list_trace.listing(hand_made_trace(), scopes.Module(HLO), top=3)
    assert got["device"] == 0
    assert [(r["op"], r["class"], r["detail"]) for r in got["longest"]] == [
        ("while.1", "panel", "lu/panel"), ("dot.1", "update", "lu/update"),
        ("all-reduce.1", "redist", "el.redist.row_permute")]
    assert got["longest"][0]["ms_a_solve"] == pytest.approx(50e-6)
    assert got["longest"][0]["events_a_solve"] == 1.0
    assert [r["op"] for r in got["unscoped"]] == ["copy.7"]


@pytest.mark.parametrize("run", [
    pytest.param(facts("hpd_solve", 4), id="cholesky-on-the-grid"),
    pytest.param(facts("lu_solve", 1), id="lu-on-one-chip"),
])
def test_silent_outside_the_pivoted_driver_on_a_grid(monkeypatch, run):
    monkeypatch.setattr(scopes, "module_texts", lambda name: [HLO])
    trace = hand_made_trace()
    assert all(r.read(trace, run) is None for r in readers().values())


def test_silent_where_the_program_has_no_such_scope(monkeypatch):
    renamed = HLO.replace("el.redist.row_permute", "el.redist.other") \
                 .replace("el.redist.MC_MR.to.STAR_STAR", "el.redist.other")
    bare = "\n".join(line.split(", metadata=")[0] for line in
                     HLO.split("\n"))
    for text in (renamed, bare):
        monkeypatch.setattr(scopes, "module_texts", lambda name, t=text: [t])
        trace = hand_made_trace()                   # a fresh cache entry
        assert all(r.read(trace, facts()) is None
                   for r in readers().values())


def test_n256_twin_of_the_cell_through_the_harness(tmp_path):
    """``lu_solve`` on the 2x2 CPU mesh, nrhs 1, through
    ``kinds/library_solve.py`` as the cell runs it; every op of the
    compiled program given 10 ns."""
    bench_dir = bench_copy.make(tmp_path / "benchmark")
    bench_copy.write_json(
        os.path.join(bench_dir, "configs", "t-lu-2x2.json"),
        {**bench_copy.CONFIGS["t-lu-1x1"], "grid": [2, 2]})
    bench_copy.write_json(
        os.path.join(bench_dir, "workloads", "t.lu.2x2.json"),
        {"config": "t-lu-2x2", "traffic": "b2b.rhs1", "chips": 4,
         "why": "test"})
    _cell, config, traffic = harness.resolve(bench_dir, "t.lu.2x2")
    kind = harness.load_module(bench_dir, "kinds", config["kind"])
    session = kind.setup(config, traffic, jax.devices()[:4], 2147483999)
    assert harness.judge([session.warm], config["limits"]) == 0
    assert session.facts["grid"] == [2, 2] and session.facts["nrhs"] == 1

    name = session.facts["solve_module"]
    ops = entry_events(session._solve.as_text())
    window = [(f"{name}(1)", 1000.0, 10.0 * len(ops))]
    trace = xplane.reduce_trace(
        {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": window}}, name)
    run = {"facts": session.facts, "peak": {"bf16_flops_per_s": 1e12}}
    metrics = harness.read_metrics(bench_dir, "layer_metrics", trace, run)
    for reader in ("row_permute_share", "panel_gather_share", "swap_share",
                   "redist_share", "collective_op_share", "panel_share",
                   "update_share", "unscoped_share"):
        assert reader in metrics, reader
    assert 0.0 < metrics["row_permute_share"]["value"] \
        < metrics["redist_share"]["value"]
    assert 0.0 < metrics["panel_gather_share"]["value"] \
        < metrics["redist_share"]["value"]
    json.dumps(metrics)                              # the line can be printed
