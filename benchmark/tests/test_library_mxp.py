"""Kind ``library_mxp`` (the mixed-precision solve, HPL-MxP's shape) on
virtual CPU devices at N = 256: through the harness from a throw-away
copy, its answer against float64 numpy and the reference's plain
implementation, an unrefined answer and an answer from a factor broken
where it is produced coming out as not correct under a limit set as the
cell's is, a program without the unpivoted route refused before it is
timed, the two readers of the ``Mixed precision`` layer on a hand-made
trace and on the kind's own facts, and ``BENCHMARK.json``'s nine cells."""
import importlib
import json
import os

import jax
import numpy as np
import pytest

import bench_copy
import reference
import reference_mxp
import run as harness
import scopes
import xplane
from test_scopes import entry_events

N, NB = 256, 64
#: set as the cell's is: the program reads 6e-9 to 1.2e-8 here and the
#: unrefined control 5e-6 to 8e-6 (``test_limit_is_set_as_the_cells_is``)
LIMIT = 2e-7
CONFIG = {"kind": "library_mxp", "operator": "mixed_solve",
          "operand": "shifted_pm1", "n": N, "dtype": "float32", "nb": NB,
          "grid": [1, 1],
          "limits": {"backward_error": {"limit": LIMIT}},
          "printed_only": {"hpl_scaled": 16.0, "refine_steps": 8.0,
                           "program_backward_error": 1.2e-7}}
CELL = {"config": "t-mxp-1x1", "traffic": "b2b.rhs1", "chips": 1,
        "why": "test"}
SEED = 2147483999

mixed = importlib.import_module("elemental_tpu.lapack.mixed")


@pytest.fixture
def bench_dir(tmp_path):
    dst = bench_copy.make(tmp_path / "benchmark")
    for name, grid, chips in (("1x1", [1, 1], 1), ("2x2", [2, 2], 4)):
        bench_copy.write_json(
            os.path.join(dst, "configs", f"t-mxp-{name}.json"),
            {**CONFIG, "grid": grid})
        bench_copy.write_json(
            os.path.join(dst, "workloads", f"t.mxp.{name}.json"),
            {**CELL, "config": f"t-mxp-{name}", "chips": chips})
    return dst


def run_cell(bench_dir, cell, chips=1):
    return harness.main(["--workload", cell, "--seed", str(SEED),
                         "--seconds", "0.2", "--trace", "0"],
                        bench_dir=bench_dir, devices=jax.devices()[:chips])


def session_of(bench_dir, cell="t.mxp.1x1", chips=1, seed=7, **kw):
    _cell, config, traffic = harness.resolve(bench_dir, cell)
    kind = harness.load_module(bench_dir, "kinds", config["kind"])
    return kind.Session(config, traffic, jax.devices()[:chips], seed, **kw)


def operands(seed, i, n=N):
    ka = np.uint32(reference.operand_key(seed, i, 0))
    kb = np.uint32(reference.operand_key(seed, i, 1))
    A = reference.plain_block(reference_mxp.entry_shifted_pm1(n, ka), 0, n, n)
    B = reference.plain_block(reference.entry_uniform_pm1(n, kb), 0, n, 1)
    return np.asarray(A, np.float64), np.asarray(B, np.float64)


def plain_solve(A, B, steps):
    """The reference's plain implementation on float32 copies, as float64."""
    return np.asarray(jax.jit(reference_mxp.plain_mixed_solve,
                              static_argnums=2)(
        A.astype(np.float32), B.astype(np.float32), steps), np.float64)


def benchmark_json():
    with open(os.path.join(os.path.dirname(bench_copy.BENCH),
                           "BENCHMARK.json")) as f:
        return json.load(f)


def backward_error(A, B, X):
    return np.linalg.norm(B - A @ X) / (
        np.linalg.norm(A) * np.linalg.norm(X) + np.linalg.norm(B))


@pytest.mark.parametrize("cell,chips", [("t.mxp.1x1", 1), ("t.mxp.2x2", 4)])
def test_run_is_correct_and_reports_every_end_to_end_metric(
        bench_dir, cell, chips, capsys):
    line = run_cell(bench_dir, cell, chips)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["metrics"]) == {"solve_s", "plan_gb", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    out = capsys.readouterr().out
    printed = [json.loads(l) for l in out.strip().splitlines()]
    assert {p["printed_only"] for p in printed if "printed_only" in p} == {
        "hpl_scaled", "refine_steps", "program_backward_error"}
    steps = [p for p in printed if p.get("printed_only") == "refine_steps"]
    assert 1 <= steps[0]["worst"] <= 8


def test_answer_matches_float64_numpy_and_the_plain_reference(bench_dir):
    """X of the timed path against ``numpy.linalg.solve`` in float64 and
    against ``reference_mxp.plain_mixed_solve`` on the same generated A
    and b; the check's number recomputed in float64 numpy; the facts."""
    import elemental_tpu as el
    session = session_of(bench_dir)
    X = session.solve(session.prepare(3))
    got = session.check(3, X)
    Xg = np.asarray(el.to_global(X), np.float64)
    A, B = operands(7, 3)
    want = np.linalg.solve(A, B)
    assert np.linalg.norm(Xg - want) < 1e-6 * np.linalg.norm(want)
    assert got["backward_error"] == pytest.approx(
        backward_error(A, B, Xg), rel=0.2)
    assert got["backward_error"] < LIMIT / 3
    assert got["hpl_scaled"] < 16.0
    assert got["refine_steps"] >= 1
    assert got["program_backward_error"] == pytest.approx(
        got["backward_error"], rel=0.5)
    assert np.linalg.norm(Xg - plain_solve(A, B, 2)) < 1e-6 * np.linalg.norm(want)
    facts = session.facts
    assert facts["flops_per_solve"] == 2 * N ** 3 / 3 + 1.5 * N ** 2
    assert facts["mxp_update_flops"] == sum(
        2.0 * (N - e) ** 2 * NB for e in range(NB, N + 1, NB))
    assert facts["lu_nopiv_steps"] == N // NB
    assert facts["mixed_updates"] == N // NB - 1
    assert facts["operator"] == "mixed_solve" and facts["n"] == N


def test_limit_is_set_as_the_cells_is(bench_dir):
    """The test's limit stands where the cell's does: three times over
    the program's largest reading, three times under the unrefined
    control's smallest (the plain reference's unrefined answer too)."""
    sound = session_of(bench_dir)
    unrefined = session_of(bench_dir, max_steps=0)
    reads, control, plain = [], [], []
    for i in range(4):
        reads.append(sound.check(i, sound.solve(sound.prepare(i))))
        control.append(unrefined.check(
            i, unrefined.solve(unrefined.prepare(i))))
        A, B = operands(7, i)
        plain.append(backward_error(A, B, plain_solve(A, B, 0)))
    assert 3 * max(r["backward_error"] for r in reads) <= LIMIT
    assert min(r["backward_error"] for r in control) >= 3 * LIMIT
    assert min(plain) >= 3 * LIMIT
    assert all(r["refine_steps"] == 0 for r in control)
    assert harness.judge(reads, CONFIG["limits"]) == 0
    assert harness.judge(control, CONFIG["limits"]) == len(control)


def test_unrefined_answer_is_not_correct(bench_dir):
    """A kind, added as a new file, that cuts the refinement to zero
    steps: ``correct`` is false and every solve counts as failed."""
    with open(os.path.join(bench_dir, "kinds", "unrefined_mxp.py"), "w") as f:
        f.write(
            "import run as harness\n"
            "def setup(config, traffic, devices, seed):\n"
            "    good = harness.load_module(%r, 'kinds', 'library_mxp')\n"
            "    return good.Session(config, traffic, devices, seed,\n"
            "                        max_steps=0)\n" % bench_dir)
    bench_copy.write_json(
        os.path.join(bench_dir, "configs", "t-unrefined-mxp.json"),
        {**CONFIG, "kind": "unrefined_mxp"})
    bench_copy.write_json(
        os.path.join(bench_dir, "workloads", "t.unrefined.mxp.json"),
        {**CELL, "config": "t-unrefined-mxp"})
    line = run_cell(bench_dir, "t.unrefined.mxp")
    assert line["correct"] is False
    assert line["failed"] == line["attempted"] >= 1


def test_broken_factor_is_not_correct(bench_dir, monkeypatch):
    """The factor broken where it is produced (every stored entry doubled:
    L's multipliers and U): no correction lowers the residual, none is
    applied, and the first solve's wrong X comes back: not correct.  (A
    factor that is only a POOR preconditioner still converges on this
    operand: with U's strict upper part dropped the loop reached the
    limit at its eighth step.  That is what refinement is, and why the
    kind also counts the unpivoted steps.)"""
    good = mixed.lu_nopiv

    def broken(A, **kw):
        LU = good(A, **kw)
        return LU.with_local(2.0 * LU.local)
    monkeypatch.setattr(mixed, "lu_nopiv", broken)
    line = run_cell(bench_dir, "t.mxp.1x1")
    assert line["correct"] is False
    assert line["failed"] == line["attempted"] >= 1


def test_a_program_without_the_unpivoted_route_is_refused(bench_dir,
                                                          monkeypatch):
    """A driver that answers through the pivoted float32 factorization
    ticks neither counter, and the kind exits before the solve is
    compiled; a program with no such entry at all exits as cleanly."""
    import elemental_tpu as el
    _cell, config, traffic = harness.resolve(bench_dir, "t.mxp.1x1")
    kind = harness.load_module(bench_dir, "kinds", config["kind"])
    traced = []

    def pivoted(A, B, **kw):
        traced.append(kw)
        return el.lu_solve(A, B, nb=kw["nb"]), {"steps": 0}
    monkeypatch.setitem(kind.OPERATORS, "mixed_solve", pivoted)
    with pytest.raises(SystemExit, match="no unpivoted factorization"):
        kind.setup(config, traffic, jax.devices()[:1], SEED)
    assert traced == [{"nb": NB}]           # traced once, nb and no more
    monkeypatch.setitem(kind.OPERATORS, "mixed_solve", None)
    with pytest.raises(SystemExit, match="has no el.mixed_solve"):
        kind.setup(config, traffic, jax.devices()[:1], SEED)


# ------------------------------------------- the Mixed precision readers

P = "jit(bench_solve)/jit(main)/el.mixed_solve/"
F = P + "factor/el.lu_nopiv/"
R = P + "el.refine/"

#: the solve in miniature: the copy of A, a step of the factor, the first
#: solve's sweep, the first residual, the loop (an event that encloses its
#: body's: a correction's sweep and a residual), a hop inside a sweep
HLO = f"""HloModule jit_bench_solve, is_scheduled=true

ENTRY %main.9 (A: f32[8,8]) -> f32[8,8] {{
  %A = f32[8,8]{{1,0}} parameter(0), metadata={{op_name="A.local"}}
  %copy.1 = f32[8,8]{{1,0}} copy(%A)
  %fusion.1 = f32[8,8]{{1,0}} fusion(%A), kind=kLoop, calls=%f, metadata={{op_name="{F}k00/diag/while/body/sub"}}
  %dot.1 = f32[8,8]{{1,0}} dot(%A, %A), metadata={{op_name="{F}k00/panel/dot_general"}}
  %convert.1 = bf16[8,8]{{1,0}} convert(%A), metadata={{op_name="{F}k00/update/convert_element_type"}}
  %dot.2 = f32[8,8]{{1,0}} dot(%convert.1, %convert.1), metadata={{op_name="{F}k00/update/dot_general"}}
  %custom-call.1 = f32[8,8]{{1,0}} custom-call(%A), metadata={{op_name="{P}sweeps/el.trsm/k00/solve/triangular_solve"}}
  %dot.3 = f32[8,8]{{1,0}} dot(%A, %A), metadata={{op_name="{P}sweeps/el.trsm/k00/update/dot_general"}}
  %fusion.2 = f32[8,8]{{1,0}} fusion(%A), kind=kLoop, calls=%f, metadata={{op_name="{R}k00/residual/el.gemm/k00/panel/dot_general"}}
  %while.1 = f32[8,8]{{1,0}} while(%A), condition=%c, body=%b, metadata={{op_name="{R}while"}}
  %custom-call.2 = f32[8,8]{{1,0}} custom-call(%A), metadata={{op_name="{R}while/body/k01/correct/el.trsm/k00/solve/triangular_solve"}}
  %dot.4 = f32[8,8]{{1,0}} dot(%A, %A), metadata={{op_name="{R}while/body/k01/correct/el.trsm/k00/update/dot_general"}}
  %fusion.3 = f32[8,8]{{1,0}} fusion(%A), kind=kLoop, calls=%f, metadata={{op_name="{R}while/body/k01/residual/el.gemm/k00/panel/dot_general"}}
  %fusion.4 = f32[8,8]{{1,0}} fusion(%A), kind=kLoop, calls=%f, metadata={{op_name="{R}while/body/k01/correct/el.trsm/k00/solve/el.redist.MC_MR.to.STAR_STAR/jit(_redistribute_jit)/copy"}}
  ROOT %copy.7 = f32[8,8]{{1,0}} copy(%while.1)
}}
"""

#: instruction -> (ns, inside the while); 1000 ns busy a solve
DURATIONS = {"copy.1": (20, False), "fusion.1": (100, False),
             "dot.1": (200, False), "convert.1": (50, False),
             "dot.2": (350, False), "custom-call.1": (30, False),
             "dot.3": (20, False), "fusion.2": (40, False),
             "custom-call.2": (60, True), "dot.4": (40, True),
             "fusion.3": (70, True), "fusion.4": (6, True),
             "copy.7": (10, False)}
WHILE_OWN = 4                    # the loop's own time, its stopping test
READERS = ("refine_share", "mxp_update_mxu_util")


def hand_made_trace(solves=2):
    ops, modules, t = [], [], 1000.0
    inside = sum(d for d, w in DURATIONS.values() if w)
    for _ in range(solves):
        start = t
        for name, (dur, in_while) in DURATIONS.items():
            if in_while and name == "custom-call.2":
                ops.append(("while.1 f32[8,8]", t,
                            float(inside + WHILE_OWN)))
            ops.append((f"{name} f32[8,8]", t, float(dur)))
            t += dur
            if name == "fusion.4":
                t += WHILE_OWN
        modules.append(("jit_bench_solve(1)", start, t - start))
        t += 500.0
    return xplane.reduce_trace(
        {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": modules}},
        "jit_bench_solve")


def run_of(operator="mixed_solve", **more):
    return {"facts": {"operator": operator, "chips": 1, "n": 8, "nrhs": 1,
                      "nb": 4, "solve_module": "jit_bench_solve",
                      "mxp_update_flops": 40000.0, **more},
            "peak": {"bf16_flops_per_s": 1e11}}


def readers(names=READERS):
    return {name: harness.load_module(bench_copy.BENCH, "layer_metrics",
                                      name) for name in names}


def test_mixed_precision_readers_on_a_hand_made_trace(monkeypatch, capsys):
    monkeypatch.setattr(scopes, "module_texts", lambda name: [HLO])
    trace, run = hand_made_trace(), run_of()
    got = {name: r.read(trace, run) for name, r in readers().items()}
    busy = 1000.0
    # refinement: the first residual 40, the body's correction 100 and
    # residual 70, the loop's own 4; the hop inside it (6) is redist
    assert got["refine_share"] == pytest.approx(100 * 214 / busy)
    # 40000 flops in the 400 ns under lu_nopiv/update are 1e11 flop/s, the peak
    assert got["mxp_update_mxu_util"] == pytest.approx(100.0)
    summary = scopes.summary(trace, run)
    assert summary["sum"] == pytest.approx(100.0)
    assert summary["seconds"]["refine/correct"] == pytest.approx(100e-9)
    assert summary["seconds"]["refine/residual"] == pytest.approx(110e-9)
    assert summary["seconds"]["refine/-"] == pytest.approx(4e-9)
    assert summary["seconds"]["lu_nopiv/update"] == pytest.approx(400e-9)
    # the first solve's sweeps alone are `sweep`; the factor's phases are
    # the generic readers'
    assert summary["share"][scopes.SWEEP] == pytest.approx(5.0)
    assert summary["share"]["update"] == pytest.approx(40.0)
    assert summary["share"]["diag"] + summary["share"]["panel"] \
        == pytest.approx(30.0)
    assert summary["share"]["unscoped"] == pytest.approx(3.0)
    assert "refine/correct" in capsys.readouterr().out


def test_mixed_precision_readers_are_silent_elsewhere(monkeypatch):
    """Another operator; a program that names nothing (a parent without
    scopes); a program whose factor names other scopes (the pivoted
    ``el.lu``) and that has no refinement; facts without the flops."""
    monkeypatch.setattr(scopes, "module_texts", lambda name: [HLO])
    trace = hand_made_trace()
    for operator in ("hpd_solve", "lu_solve", "herm_eig", "least_squares"):
        assert all(r.read(trace, run_of(operator)) is None
                   for r in readers().values()), operator
    no_flops = run_of()
    del no_flops["facts"]["mxp_update_flops"]
    assert readers()["mxp_update_mxu_util"].read(trace, no_flops) is None
    bare = "\n".join(line.split(", metadata=")[0] for line in
                     HLO.split("\n"))
    monkeypatch.setattr(scopes, "module_texts", lambda name: [bare])
    trace = hand_made_trace()                       # a fresh cache entry
    assert all(r.read(trace, run_of()) is None for r in readers().values())
    pivoted = HLO.replace("el.lu_nopiv", "el.lu").replace(
        "el.refine/while/body/k01/correct", "sweeps").replace(
        "el.refine/while/body/k01/residual", "sweeps").replace(
        "el.refine/k00/residual", "sweeps").replace("el.refine/", "")
    monkeypatch.setattr(scopes, "module_texts", lambda name: [pivoted])
    trace = hand_made_trace()
    assert all(r.read(trace, run_of()) is None for r in readers().values())


def test_every_reader_reads_the_kinds_facts(bench_dir):
    """Every file under ``layer_metrics/`` called on the facts of a real
    session, every op of the compiled program's entry given 10 ns: no
    reader asks the kind for a key it does not give, and the line holds
    every per-layer metric ``BENCHMARK.json`` says the cell owes."""
    harness.enable_cache()      # as main does: the set-up readers' log
    session = session_of(bench_dir, seed=SEED)
    assert harness.judge([session.warm], CONFIG["limits"]) == 0
    name = session.facts["solve_module"]
    assert name == "jit_bench_solve"
    ops = entry_events(session._solve.as_text())
    window = [(f"{name}(1)", 1000.0, 10.0 * len(ops))]
    trace = xplane.reduce_trace(
        {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": window}}, name)
    run = {"facts": session.facts, "setup_s": 1.0,
           "peak": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}
    metrics = harness.read_metrics(bench_dir, "layer_metrics", trace, run)
    for reader in ("device_idle_share", "flops_util", "hlo_lines",
                   "panel_share", "update_share", "sweep_share",
                   "unscoped_share", "plan_shards", "refine_share",
                   "mxp_update_mxu_util"):
        assert reader in metrics, reader
    assert metrics["refine_share"]["value"] > 0.0
    assert metrics["mxp_update_mxu_util"]["value"] > 0.0
    assert metrics["sweep_share"]["value"] > 0.0
    assert metrics["update_share"]["value"] > 0.0
    for reader in ("hemv_share", "dc_share", "backtransform_share",
                   "hemv_hbm_util", "row_permute_share",
                   "panel_gather_share", "tsqr_local_share",
                   "tsqr_tree_share", "lstsq_hbm_util"):
        assert reader not in metrics, reader
    per_layer = benchmark_json()["per_layer"]
    cell = "hplmxp.1x1.b2b"
    owed = {m["name"] for m in per_layer if cell in m.get("workloads", [cell])}
    assert {"refine_share", "mxp_update_mxu_util", "plan_shards"} <= owed
    assert owed <= set(metrics), sorted(owed - set(metrics))


def test_benchmark_has_nine_cells_and_four_on_four_chips():
    cells = benchmark_json()["workloads"]
    assert len(cells) == 9 and cells[-1]["name"] == "hplmxp.1x1.b2b"
    four = sum(c["chips"] == 4 for c in cells)
    assert four == 4 <= max(1, len(cells) // 2)
    _cell, config, traffic = harness.resolve(bench_copy.BENCH,
                                             "hplmxp.1x1.b2b")
    assert config["kind"] == "library_mxp" and config["n"] == 32768
    assert traffic["nrhs"] == 1
    assert config["limits"]["backward_error"]["limit"] <= 1e-8
    assert sorted(config["reduced"]) == ["n"]
