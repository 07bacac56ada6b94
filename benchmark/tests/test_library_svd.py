"""Kind ``library_svd`` (the dense SVD, every triplet of a square matrix)
on a virtual CPU device at N = 256: through the harness from a throw-away
copy, its check against float64 numpy, ``reference_svd`` failing what it
must, a program that takes another route refused, and the five readers of
the polar stage on a hand-made trace and on the kind's own facts."""
import os

import jax
import numpy as np
import pytest

import bench_copy
import reference
import reference_svd
import run as harness
import scopes
import svd_share
import xplane
from test_scopes import entry_events

N = 256
CONFIG = {"kind": "library_svd", "operator": "svd",
          "operand": "uniform_pm1", "n": N, "dtype": "float32",
          "grid": [1, 1],
          "limits": {"residual": {"limit": 1e-6},
                     "orthogonality_u": {"limit": 1e-4},
                     "orthogonality_v": {"limit": 1e-4},
                     "descents": {"limit": 0}},
          "printed_only": {"frobenius_defect": 1e-5}}
CELL = {"config": "t-svd-1x1", "traffic": "b2b.full", "chips": 1,
        "why": "test"}
SEED = 2147483999


@pytest.fixture
def bench_dir(tmp_path):
    dst = bench_copy.make(tmp_path / "benchmark")
    bench_copy.write_json(os.path.join(dst, "configs", "t-svd-1x1.json"),
                          CONFIG)
    bench_copy.write_json(os.path.join(dst, "workloads", "t.svd.1x1.json"),
                          CELL)
    return dst


def run_cell(bench_dir, cell):
    return harness.main(["--workload", cell, "--seed", str(SEED),
                         "--seconds", "0.2", "--trace", "0"],
                        bench_dir=bench_dir, devices=jax.devices()[:1])


def entry_of(seed, i):
    key = np.uint32(reference.operand_key(seed, i, 0))
    return reference.ENTRIES["uniform_pm1"](N, key)


def operand(seed, i):
    return np.asarray(reference.plain_block(entry_of(seed, i), 0, N, N),
                      np.float64)


def numbers(entry, U, s, V):
    return {k: float(v) for k, v in reference_svd.residuals_svd(
        entry, N, U, s, V).items()}


def test_run_is_correct_and_reports_every_end_to_end_metric(bench_dir):
    line = run_cell(bench_dir, "t.svd.1x1")
    assert line["correct"] and line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["metrics"]) == {"solve_s", "plan_gb", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())


def test_answer_matches_float64_numpy(bench_dir):
    """``(U, s, V)`` of the timed path against ``numpy.linalg.svd`` in
    float64 on the same generated A: singular values to 50 eps ||A||_2,
    and the check's numbers recomputed in float64 numpy."""
    import elemental_tpu as el
    _cell, config, traffic = harness.resolve(bench_dir, "t.svd.1x1")
    kind = harness.load_module(bench_dir, "kinds", config["kind"])
    session = kind.setup(config, traffic, jax.devices()[:1], 7)
    U, s, V = session.solve(session.prepare(3))
    got = session.check(3, (U, s, V))
    s = np.asarray(s, np.float64)
    Ug = np.asarray(el.to_global(U), np.float64)
    Vg = np.asarray(el.to_global(V), np.float64)
    A = operand(7, 3)
    want = np.linalg.svd(A, compute_uv=False)
    eps = np.finfo(np.float32).eps
    assert np.abs(s - want).max() <= 50 * eps * want[0]
    residual = np.linalg.norm(A @ Vg - Ug * s) / (
        np.linalg.norm(A) * np.linalg.norm(Vg))
    assert got["residual"] == pytest.approx(residual, rel=0.05)
    for name, Q in (("orthogonality_u", Ug), ("orthogonality_v", Vg)):
        want_q = np.linalg.norm(Q.T @ Q - np.eye(N)) / np.sqrt(N)
        assert got[name] == pytest.approx(want_q, rel=0.05)
    assert got["descents"] == 0.0
    assert got["frobenius_defect"] < 1e-5
    # float32: 2 QR-based and 4 Cholesky-based steps, 44 n^3 and the
    # eigensolve's 14 n^3 / 3
    facts = session.facts
    assert facts["polar_flops"] == pytest.approx(44.0 * N ** 3)
    assert facts["flops_per_solve"] == pytest.approx((44 + 14 / 3) * N ** 3)
    assert facts["qdwh_step"] == {"kind=qr": 2, "kind=chol": 4}
    assert facts["svd_route"] == {"approach=polar": 1}
    assert set(facts["polar_block"]) == {
        "nb=128,stage=qr", "nb=128,stage=chol", "nb=128,stage=eig"}


def test_the_schedule_rule_is_the_programs():
    """The benchmark's own copy of the step kinds against the program's
    schedule, in float32 and in float64."""
    from elemental_tpu.lapack.funcs import _qdwh_schedule
    for eps in (2.0 ** -23, 2.0 ** -52):
        program = ["qr" if c > 100.0 else "chol"
                   for _a, _b, c in _qdwh_schedule(eps, 10 * eps)]
        assert reference_svd.qdwh_step_kinds(eps) == program
    assert reference_svd.qdwh_step_kinds() == ["qr"] * 2 + ["chol"] * 4


def float64_answer(seed, i):
    U, s, Vt = np.linalg.svd(operand(seed, i))
    return (U.astype(np.float32), s.astype(np.float32),
            Vt.T.astype(np.float32))


def test_residuals_svd_reads_a_float64_answer_as_rounding():
    entry = entry_of(5, 0)
    good = numbers(entry, *float64_answer(5, 0))
    assert good["residual"] < 1e-7
    assert good["orthogonality_u"] < 1e-6 and good["orthogonality_v"] < 1e-6
    assert good["descents"] == 0.0 and good["frobenius_defect"] < 1e-6


def swapped_v(U, s, V):
    V = V.copy()
    V[:, [0, N - 1]] = V[:, [N - 1, 0]]
    return U, s, V


def negated_u(U, s, V):
    U = U.copy()
    U[:, 3] = -U[:, 3]
    return U, s, V


def unsorted_s(U, s, V):
    order = np.arange(N)
    order[[1, 2]] = [2, 1]                  # a consistent answer, unsorted
    return U[:, order], s[order], V[:, order]


def repeated_u(U, s, V):
    U = U.copy()
    U[:, 1] = U[:, 0]
    return U, s, V


def negative_s(U, s, V):
    U, s = U.copy(), s.copy()               # A = U S V^T still, s_n < 0
    U[:, -1], s[-1] = -U[:, -1], -s[-1]
    return U, s, V


#: how an answer is wrong -> (the number that must fail, its least reading)
WRONG = {swapped_v: ("residual", 1e-3), negated_u: ("residual", 1e-3),
         unsorted_s: ("descents", 1.0), repeated_u: ("orthogonality_u", 1e-2),
         negative_s: ("descents", 1.0)}


@pytest.mark.parametrize("how", WRONG, ids=lambda f: f.__name__)
def test_residuals_svd_fails_a_wrong_answer(how):
    """Each way of being wrong reads over its limit by orders, and leaves
    the numbers it does not touch at rounding."""
    entry = entry_of(5, 0)
    got = numbers(entry, *how(*float64_answer(5, 0)))
    name, least = WRONG[how]
    assert got[name] >= least
    assert harness.judge([got], CONFIG["limits"]) == 1
    if name == "descents":              # a decomposition of A all the same
        assert got["residual"] < 1e-7 and got["orthogonality_u"] < 1e-6
    assert got["orthogonality_v"] < 1e-6


def test_residuals_svd_refuses_a_subset():
    U, s, V = float64_answer(5, 0)
    with pytest.raises(ValueError):
        reference_svd.residuals_svd(entry_of(5, 0), N, U[:, :8], s[:8],
                                    V[:, :8])


def test_a_lower_precision_answer_fails_the_residual(bench_dir):
    """The kind's own control, ``precision=Precision.HIGH`` (three bf16
    passes; on the CPU backend the flag changes nothing, so the products'
    operands are rounded the way three passes leave them: to 16 bits of
    mantissa): the residual reads over the program's by more than three
    times."""
    import elemental_tpu as el
    _cell, config, traffic = harness.resolve(bench_dir, "t.svd.1x1")
    kind = harness.load_module(bench_dir, "kinds", config["kind"])
    session = kind.setup(config, traffic, jax.devices()[:1], SEED)
    sound = session.warm
    U, s, V = session.solve(session.prepare(0))

    def rounded(X):
        bits = np.asarray(el.to_global(X)).view(np.uint32)
        return ((bits + 0x80) & 0xFFFFFF00).view(np.float32)
    low = numbers(entry_of(SEED, 0), rounded(U), np.asarray(s), rounded(V))
    assert low["residual"] > 3 * sound["residual"]
    assert harness.judge([sound], config["limits"]) == 0


def test_a_program_on_another_route_is_refused(bench_dir, monkeypatch):
    """A driver behind the same name that ticks no
    ``svd_route{approach=polar}`` (here: the Golub-Kahan route), and one
    that reads a value on the host while it is traced (the parent's), are
    refused before the solve is compiled."""
    import elemental_tpu as el
    _cell, config, traffic = harness.resolve(bench_dir, "t.svd.1x1")
    kind = harness.load_module(bench_dir, "kinds", config["kind"])
    monkeypatch.setitem(kind.OPERATORS, "svd",
                        lambda A: el.svd(A, approach="golub"))
    with pytest.raises(SystemExit, match="svd_route"):
        kind.setup(config, traffic, jax.devices()[:1], SEED)
    monkeypatch.setitem(kind.OPERATORS, "svd",
                        lambda A: el.svd(A) if float(A.local[0, 0]) else A)
    with pytest.raises(SystemExit, match="reads a value on the host"):
        kind.setup(config, traffic, jax.devices()[:1], SEED)


def test_a_mix_that_asks_for_a_subset_is_refused(bench_dir):
    _cell, config, traffic = harness.resolve(bench_dir, "t.svd.1x1")
    kind = harness.load_module(bench_dir, "kinds", config["kind"])
    with pytest.raises(ValueError, match="every triplet"):
        kind.setup(config, {**traffic, "subset": "index"},
                   jax.devices()[:1], SEED)


# ------------------------------------------------ the Polar SVD readers

S = "jit(bench_solve)/jit(main)/el.svd/"
Q = S + "el.polar/"

#: the SVD in miniature: the scale, the QR-based steps' loop body (a panel
#: of its qr, an update, the apply_q's product outside any phase, Q1 Q2^T),
#: the Cholesky-based steps' (herk, cholesky, a trsm), H, the inner eigensolve
#: (a matvec and a back-transform panel), the reversal, U, a compiler's copy
HLO = f"""HloModule jit_bench_solve, is_scheduled=true

ENTRY %main.9 (A: f32[8,8]) -> f32[8,8] {{
  %A = f32[8,8]{{1,0}} parameter(0), metadata={{op_name="A.local"}}
  %fusion.1 = f32[8,8]{{1,0}} fusion(%A), kind=kLoop, calls=%f, metadata={{op_name="{Q}div"}}
  %fusion.2 = f32[8,8]{{1,0}} fusion(%A), kind=kLoop, calls=%f, metadata={{op_name="{Q}while/body/closed_call/qdwh_qr01_02/el.qr/k03/panel/while/body/mul"}}
  %dot.1 = f32[8,8]{{1,0}} dot(%A, %A), metadata={{op_name="{Q}while/body/closed_call/qdwh_qr01_02/el.qr/k03/update/dot_general"}}
  %dot.2 = f32[8,8]{{1,0}} dot(%A, %A), metadata={{op_name="{Q}while/body/closed_call/qdwh_qr01_02/dot_general"}}
  %dot.3 = f32[8,8]{{1,0}} dot(%A, %A), metadata={{op_name="{Q}while/body/closed_call/qdwh_qr01_02/el.gemm/k00/panel/dot_general"}}
  %dot.4 = f32[8,8]{{1,0}} dot(%A, %A), metadata={{op_name="{Q}while/body/closed_call/qdwh_chol03_06/el.herk/k00/update/dot_general"}}
  %fusion.3 = f32[8,8]{{1,0}} fusion(%A), kind=kLoop, calls=%f, metadata={{op_name="{Q}while/body/closed_call/qdwh_chol03_06/el.cholesky/k01/diag/cholesky"}}
  %fusion.4 = f32[8,8]{{1,0}} fusion(%A), kind=kLoop, calls=%f, metadata={{op_name="{Q}while/body/closed_call/qdwh_chol03_06/el.trsm/k02/solve/triangular_solve"}}
  %dot.5 = f32[8,8]{{1,0}} dot(%A, %A), metadata={{op_name="{Q}polar_h/el.gemm/k00/panel/dot_general"}}
  %fusion.5 = f32[8,8]{{1,0}} fusion(%A), kind=kLoop, calls=%f, metadata={{op_name="{S}el.herm_eig/el.hermitian_tridiag/k00/hemv/dot_general"}}
  %dot.6 = f32[8,8]{{1,0}} dot(%A, %A), metadata={{op_name="{S}el.herm_eig/el.apply_q_herm_tridiag/k31/apply/dot_general"}}
  %fusion.6 = f32[8,8]{{1,0}} fusion(%A), kind=kLoop, calls=%f, metadata={{op_name="{S}rev"}}
  %dot.7 = f32[8,8]{{1,0}} dot(%A, %A), metadata={{op_name="{S}svd_u/el.gemm/k00/panel/dot_general"}}
  ROOT %copy.7 = f32[8,8]{{1,0}} copy(%dot.7)
}}
"""

#: instruction -> ns; 400 ns busy a solve
DURATIONS = {"fusion.1": 2, "fusion.2": 60, "dot.1": 40, "dot.2": 50,
             "dot.3": 20, "dot.4": 30, "fusion.3": 10, "fusion.4": 20,
             "dot.5": 14, "fusion.5": 80, "dot.6": 40, "fusion.6": 4,
             "dot.7": 16, "copy.7": 14}
READERS = ("polar_share", "qdwh_qr_share", "qdwh_chol_share",
           "svd_eig_share", "polar_mxu_util")


def hand_made_trace(solves=2):
    ops, modules, t = [], [], 1000.0
    for _ in range(solves):
        start = t
        for name, dur in DURATIONS.items():
            ops.append((f"{name} f32[8,8]", t, float(dur)))
            t += dur
        modules.append(("jit_bench_solve(1)", start, t - start))
        t += 500.0
    return xplane.reduce_trace(
        {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": modules}},
        "jit_bench_solve")


def run_of(operator="svd", **more):
    return {"facts": {"operator": operator, "chips": 1,
                      "solve_module": "jit_bench_solve",
                      "polar_flops": 13100.0, **more},
            "peak": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 100e9}}


def readers():
    return {name: harness.load_module(bench_copy.BENCH, "layer_metrics",
                                      name) for name in READERS}


def test_polar_readers_on_a_hand_made_trace(monkeypatch, capsys):
    monkeypatch.setattr(scopes, "module_texts", lambda name: [HLO])
    trace, run = hand_made_trace(), run_of()
    got = {name: r.read(trace, run) for name, r in readers().items()}
    assert sum(DURATIONS.values()) == 400
    # 2 of the scale, 170 of the QR-based steps, 60 of the Cholesky-based
    # ones, 14 of H, 16 of U
    assert got["polar_share"] == pytest.approx(100 * 262 / 400)
    assert got["qdwh_qr_share"] == pytest.approx(100 * 170 / 400)
    assert got["qdwh_chol_share"] == pytest.approx(100 * 60 / 400)
    assert got["svd_eig_share"] == pytest.approx(100 * 120 / 400)
    # 13100 flops in 262 ns are 5e10 a second, 5 % of the 1e12 peak
    assert got["polar_mxu_util"] == pytest.approx(5.0)
    stages = svd_share.summary(trace, run)
    assert stages["polar_rest"] == pytest.approx(2e-9)
    assert stages["svd_rest"] == pytest.approx(4e-9)
    # the nested drivers' ops keep their own phase in scopes.py's classes:
    # panel 60 (qr) + 20 + 14 + 16 (the three gemms), update 40 + 30,
    # diag 10, solve 20, hemv 80, apply 40; other 2 + 50 + 4; unscoped 14
    summary = scopes.summary(trace, run)
    assert summary["share"]["panel"] == pytest.approx(100 * 110 / 400)
    assert summary["share"]["update"] == pytest.approx(100 * 70 / 400)
    assert summary["share"]["other"] == pytest.approx(100 * 56 / 400)
    assert summary["share"]["unscoped"] == pytest.approx(100 * 14 / 400)
    assert summary["seconds"]["qr/panel"] == pytest.approx(60e-9)
    assert summary["seconds"]["polar/-"] == pytest.approx(52e-9)
    out = capsys.readouterr().out
    assert '"svd_stages"' in out and '"qdwh_chol03_06"' in out
    assert '"qdwh_qr01_02"' in out
    # the stages and what lies outside el.svd are all of the busy time
    assert sum(stages.values()) == pytest.approx(386e-9)


def test_polar_readers_are_silent_elsewhere(monkeypatch):
    """Another operator; a program that names nothing; a program whose
    scopes hold no ``el.svd`` (the parent's drivers, had it compiled); a
    kind whose facts lack the flops."""
    monkeypatch.setattr(scopes, "module_texts", lambda name: [HLO])
    trace = hand_made_trace()
    assert all(r.read(trace, run_of("herm_eig")) is None
               for r in readers().values())
    no_flops = run_of()
    del no_flops["facts"]["polar_flops"]
    assert readers()["polar_mxu_util"].read(trace, no_flops) is None
    bare = "\n".join(line.split(", metadata=")[0] for line in
                     HLO.split("\n"))
    monkeypatch.setattr(scopes, "module_texts", lambda name: [bare])
    trace = hand_made_trace()                       # a fresh cache entry
    assert all(r.read(trace, run_of()) is None for r in readers().values())
    other = HLO.replace("el.svd/", "").replace("el.polar/", "")
    monkeypatch.setattr(scopes, "module_texts", lambda name: [other])
    trace = hand_made_trace()
    assert all(r.read(trace, run_of()) is None for r in readers().values())


def test_every_reader_reads_the_kinds_facts(bench_dir):
    """Every file under ``layer_metrics/`` called on the facts of a real
    session, every op of the compiled program's entry given 10 ns: no
    reader asks the kind for a key it does not give, and the line holds
    every metric the cell owes."""
    _cell, config, traffic = harness.resolve(bench_dir, "t.svd.1x1")
    kind = harness.load_module(bench_dir, "kinds", config["kind"])
    session = kind.setup(config, traffic, jax.devices()[:1], SEED)
    assert harness.judge([session.warm], config["limits"]) == 0
    for key in ("operator", "n", "grid", "chips", "solve_module",
                "flops_per_solve", "polar_flops", "plan_bytes",
                "plan_parts", "hlo_lines", "collectives", "svd_route",
                "qdwh_step", "polar_block"):
        assert key in session.facts, key
    assert "nb" not in session.facts
    name = session.facts["solve_module"]
    ops = entry_events(session._solve.as_text())
    window = [(f"{name}(1)", 1000.0, 10.0 * len(ops))]
    trace = xplane.reduce_trace(
        {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": window}}, name)
    run = {"facts": session.facts, "setup_s": 1.0,
           "peak": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}
    metrics = harness.read_metrics(bench_dir, "layer_metrics", trace, run)
    for reader in ("device_idle_share", "flops_util", "hlo_lines",
                   "plan_shards", "panel_share", "update_share",
                   "sweep_share", "unscoped_share") + READERS:
        assert reader in metrics, reader
    shares = {r: metrics[r]["value"] for r in READERS}
    assert all(v > 0.0 for v in shares.values())
    assert shares["polar_share"] >= (shares["qdwh_qr_share"]
                                     + shares["qdwh_chol_share"])
    assert (shares["polar_share"] + shares["svd_eig_share"]
            + metrics["unscoped_share"]["value"]) == pytest.approx(
                100.0, abs=5.0)
    for reader in ("hemv_share", "dc_share", "backtransform_share",
                   "hemv_hbm_util", "refine_share", "tsqr_local_share"):
        assert reader not in metrics, reader


def test_the_cell_is_on_every_list_it_owes():
    """``BENCHMARK.json``: the cell, its configuration, and the cell's name
    on the list of every per-layer metric its traced line reports that
    keeps a list."""
    import json
    with open(os.path.join(os.path.dirname(bench_copy.BENCH),
                           "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(c for c in bench["workloads"] if c["name"] == "svd.1x1.b2b")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "svd-full-1x1", "b2b.full", 1)
    config = next(c for c in bench["configs"] if c["name"] == "svd-full-1x1")
    assert config["reduced"] == ["n"]
    listed = {m["name"] for m in bench["per_layer"]
              if "svd.1x1.b2b" in m.get("workloads", ())}
    assert listed == set(READERS) | {"plan_shards"}
    on_disk = harness.load_json(bench_copy.BENCH, "configs", "svd-full-1x1")
    assert "nb" not in on_disk and on_disk["kind"] == "library_svd"
