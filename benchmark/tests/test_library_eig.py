"""Kind ``library_eig`` (the Hermitian eigensolve, full spectrum with
vectors) on a virtual CPU device at N = 256: through the harness from a
throw-away copy, its check against float64 numpy, answers broken where
they are produced coming out as not correct, and the four readers of the
``Spectral`` layer on a hand-made trace and on the kind's own facts."""
import os
import re

import jax
import numpy as np
import pytest

import bench_copy
import reference
import reference_eig
import run as harness
import scopes
import xplane
from test_scopes import entry_events

N = 256
CONFIG = {"kind": "library_eig", "operator": "herm_eig",
          "operand": "hpd_shifted", "n": N, "dtype": "float32", "nb": 64,
          "grid": [1, 1],
          "limits": {"residual": {"limit": 1e-6},
                     "orthogonality": {"limit": 1e-4},
                     "descents": {"limit": 0}}}
CELL = {"config": "t-heig-1x1", "traffic": "b2b.full", "chips": 1,
        "why": "test"}
SEED = 2147483999


@pytest.fixture
def bench_dir(tmp_path):
    dst = bench_copy.make(tmp_path / "benchmark")
    bench_copy.write_json(os.path.join(dst, "configs", "t-heig-1x1.json"),
                          CONFIG)
    bench_copy.write_json(os.path.join(dst, "workloads", "t.heig.1x1.json"),
                          CELL)
    return dst


def run_cell(bench_dir, cell):
    return harness.main(["--workload", cell, "--seed", str(SEED),
                         "--seconds", "0.2", "--trace", "0"],
                        bench_dir=bench_dir, devices=jax.devices()[:1])


def operand(seed, i):
    key = np.uint32(reference.operand_key(seed, i, 0))
    return np.asarray(reference.plain_block(
        reference.ENTRIES["hpd_shifted"](N, key), 0, N, N), np.float64)


def test_run_is_correct_and_reports_every_end_to_end_metric(bench_dir):
    line = run_cell(bench_dir, "t.heig.1x1")
    assert line["correct"] and line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["metrics"]) == {"solve_s", "plan_gb", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())


def test_answer_matches_float64_numpy(bench_dir):
    """``(w, Z)`` of the timed path against ``numpy.linalg.eigh`` in
    float64 on the same generated A: eigenvalues to 50 eps ||A||_2 (a
    backward-stable float32 method), and the check's numbers recomputed
    in float64 numpy."""
    import elemental_tpu as el
    _cell, config, traffic = harness.resolve(bench_dir, "t.heig.1x1")
    kind = harness.load_module(bench_dir, "kinds", config["kind"])
    session = kind.setup(config, traffic, jax.devices()[:1], 7)
    w, Z = session.solve(session.prepare(3))
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
    assert session.facts["flops_per_solve"] == 14 * N ** 3 / 3
    # the stored triangles of the trailing matrices of order 1 .. N - 1
    assert session.facts["hemv_bytes"] == 4 * sum(
        m * (m + 1) // 2 for m in range(1, N))


def test_residuals_eig_reads_a_float64_answer_as_rounding(bench_dir):
    """numpy's float64 eigenpairs, rounded to float32, read at float32
    rounding; each way of being wrong reads over it by orders."""
    key = np.uint32(reference.operand_key(5, 0, 0))
    entry = reference.ENTRIES["hpd_shifted"](N, key)
    w, Z = np.linalg.eigh(operand(5, 0))
    w, Z = w.astype(np.float32), Z.astype(np.float32)

    def numbers(w, Z):
        return {k: float(v) for k, v in
                reference_eig.residuals_eig(entry, N, w, Z).items()}
    good = numbers(w, Z)
    assert good["residual"] < 1e-7 and good["orthogonality"] < 1e-6
    assert good["descents"] == 0.0
    swapped = Z.copy()
    swapped[:, [0, N - 1]] = swapped[:, [N - 1, 0]]
    assert numbers(w, swapped)["residual"] > 1e-3
    assert numbers(w, swapped)["orthogonality"] < 1e-6
    assert numbers(w, 1.001 * Z)["orthogonality"] > 1e-3
    doubled = Z.copy()
    doubled[:, 1] = doubled[:, 0]               # a vector missing
    assert numbers(w, doubled)["orthogonality"] > 1e-2
    assert numbers(w[::-1].copy(), Z[:, ::-1].copy())["descents"] == N - 1
    with pytest.raises(ValueError):             # a subset is not the answer
        reference_eig.residuals_eig(entry, N, w[:8], Z[:, :8])


BROKEN = {
    "swap": "Z.with_local(Z.local.at[:, 0].set(Z.local[:, -1])"
            ".at[:, -1].set(Z.local[:, 0]))",
    "shift": "Z",
    "scale": "Z.with_local(Z.local * 1.001)",
}


@pytest.mark.parametrize("how", sorted(BROKEN))
def test_broken_timed_path_is_not_correct(bench_dir, how):
    """A kind, added as a new file, whose solve alters the answer where it
    is produced (two columns of Z swapped; one eigenvalue moved by
    1e-3 ||A||_F; Z scaled): ``correct`` is false and every solve counts
    as failed."""
    a_norm = np.linalg.norm(operand(SEED, 0))
    shift = f"w.at[{N // 2}].add({1e-3 * a_norm})" if how == "shift" else "w"
    with open(os.path.join(bench_dir, "kinds", "broken_eig.py"), "w") as f:
        f.write(
            "import run as harness\n"
            "def setup(config, traffic, devices, seed):\n"
            "    good = harness.load_module(%r, 'kinds', 'library_eig')\n"
            "    session = good.setup(config, traffic, devices, seed)\n"
            "    solve = session.solve\n"
            "    def altered(operands):\n"
            "        w, Z = solve(operands)\n"
            "        return %s, %s\n"
            "    session.solve = altered\n"
            "    return session\n" % (bench_dir, shift, BROKEN[how]))
    bench_copy.write_json(
        os.path.join(bench_dir, "configs", "t-broken-eig.json"),
        {**CONFIG, "kind": "broken_eig"})
    bench_copy.write_json(
        os.path.join(bench_dir, "workloads", "t.broken.eig.json"),
        {**CELL, "config": "t-broken-eig"})
    line = run_cell(bench_dir, "t.broken.eig")
    assert line["correct"] is False
    assert line["failed"] == line["attempted"] >= 1


def test_a_mix_that_asks_for_a_subset_is_refused(bench_dir):
    _cell, config, traffic = harness.resolve(bench_dir, "t.heig.1x1")
    kind = harness.load_module(bench_dir, "kinds", config["kind"])
    with pytest.raises(ValueError, match="full spectrum"):
        kind.setup(config, {**traffic, "subset": "index"},
                   jax.devices()[:1], SEED)


# ------------------------------------------------- the Spectral readers

P = "jit(bench_solve)/jit(main)/el.herm_eig/"
T = P + "el.hermitian_tridiag/jit(_tridiag_panel)/while/body/closed_call/"
D = P + "el.tridiag_eig/jit(_tridiag_eig_jit)/"

#: the eigensolve in miniature: a panel's column loop (the matvec beside
#: the rest), a rank-2k update, a leaf, a secular solve, a distributed
#: merge whose gemm nests its own panel, the fill of the eigenvector
#: matrix outside a phase, a back-transform panel, a compiler's copy
HLO = f"""HloModule jit_bench_solve, is_scheduled=true

ENTRY %main.9 (A: f32[8,8]) -> f32[8,8] {{
  %A = f32[8,8]{{1,0}} parameter(0), metadata={{op_name="A.local"}}
  %fusion.1 = f32[8,8]{{1,0}} fusion(%A), kind=kLoop, calls=%f, metadata={{op_name="{T}k00/hemv/dot_general"}}
  %fusion.2 = f32[8,8]{{1,0}} fusion(%A), kind=kLoop, calls=%f, metadata={{op_name="{T}k00/panel/mul"}}
  %dot.1 = f32[8,8]{{1,0}} dot(%A, %A), metadata={{op_name="{P}el.hermitian_tridiag/k00/update/dot_general"}}
  %custom-call.1 = f32[8,8]{{1,0}} custom-call(%A), metadata={{op_name="{D}k00/leaf/eigh"}}
  %fusion.3 = f32[8,8]{{1,0}} fusion(%A), kind=kLoop, calls=%f, metadata={{op_name="{D}k04/secular/while/body/div"}}
  %dot.2 = f32[8,8]{{1,0}} dot(%A, %A), metadata={{op_name="{D}k04/merge/el.gemm/k00/panel/dot_general"}}
  %fusion.4 = f32[8,8]{{1,0}} fusion(%A), kind=kLoop, calls=%f, metadata={{op_name="{D}gather"}}
  %dot.3 = f32[8,8]{{1,0}} dot(%A, %A), metadata={{op_name="{P}el.apply_q_herm_tridiag/k31/apply/dot_general"}}
  ROOT %copy.7 = f32[8,8]{{1,0}} copy(%dot.3)
}}
"""

#: instruction -> ns; 200 ns busy a solve
DURATIONS = {"fusion.1": 80, "fusion.2": 30, "dot.1": 10, "custom-call.1": 4,
             "fusion.3": 16, "dot.2": 20, "fusion.4": 6, "dot.3": 30,
             "copy.7": 4}
READERS = ("hemv_share", "dc_share", "backtransform_share", "hemv_hbm_util")


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


def run_of(operator="herm_eig", **more):
    return {"facts": {"operator": operator, "chips": 1,
                      "solve_module": "jit_bench_solve",
                      "hemv_bytes": 4000.0, **more},
            "peak": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 100e9}}


def readers():
    return {name: harness.load_module(bench_copy.BENCH, "layer_metrics",
                                      name) for name in READERS}


def test_spectral_readers_on_a_hand_made_trace(monkeypatch, capsys):
    monkeypatch.setattr(scopes, "module_texts", lambda name: [HLO])
    trace, run = hand_made_trace(), run_of()
    got = {name: r.read(trace, run) for name, r in readers().items()}
    assert got["hemv_share"] == pytest.approx(100 * 80 / 200)
    # leaf 4, secular 16, merge 20 (its gemm's panel is the merge's), and
    # the 6 ns of the stage outside a phase
    assert got["dc_share"] == pytest.approx(100 * 46 / 200)
    assert got["backtransform_share"] == pytest.approx(100 * 30 / 200)
    # 4000 bytes in 80 ns are 50 GB/s of the 100 GB/s peak
    assert got["hemv_hbm_util"] == pytest.approx(50.0)
    summary = scopes.summary(trace, run)
    assert summary["sum"] == pytest.approx(100.0)
    assert summary["seconds"]["hermitian_tridiag/hemv"] == pytest.approx(80e-9)
    assert summary["share"]["panel"] == pytest.approx(15.0)
    assert summary["share"]["update"] == pytest.approx(5.0)
    assert summary["share"]["unscoped"] == pytest.approx(2.0)
    assert "tridiag_eig/merge" in capsys.readouterr().out


def test_spectral_readers_are_silent_elsewhere(monkeypatch):
    """Another operator; a program that names nothing (the parent of the
    PR that added the scopes); a peak table without the bandwidth; a kind
    whose facts lack the bytes."""
    monkeypatch.setattr(scopes, "module_texts", lambda name: [HLO])
    trace = hand_made_trace()
    assert all(r.read(trace, run_of("hpd_solve")) is None
               for r in readers().values())
    no_peak = {**run_of(), "peak": {"bf16_flops_per_s": 1e12}}
    assert readers()["hemv_hbm_util"].read(trace, no_peak) is None
    no_bytes = run_of()
    del no_bytes["facts"]["hemv_bytes"]
    assert readers()["hemv_hbm_util"].read(trace, no_bytes) is None
    bare = "\n".join(line.split(", metadata=")[0] for line in
                     HLO.split("\n"))
    monkeypatch.setattr(scopes, "module_texts", lambda name: [bare])
    trace = hand_made_trace()                       # a fresh cache entry
    assert all(r.read(trace, run_of()) is None for r in readers().values())
    # scopes of other drivers only, as the parent's eigensolve has them
    # (el.gemm, el.redist.*): no stage to read, and no error
    other = re.sub(r"el\.(herm_eig|hermitian_tridiag|tridiag_eig|"
                   r"apply_q_herm_tridiag)/", "", HLO)
    other = re.sub(r"(?<!el\.gemm/)k\d\d/\w+/", "", other)
    assert "el.gemm/k00/panel" in other and "merge" not in other
    monkeypatch.setattr(scopes, "module_texts", lambda name: [other])
    trace = hand_made_trace()
    got = {name: r.read(trace, run_of()) for name, r in readers().items()}
    assert got["dc_share"] is None and got["backtransform_share"] is None
    assert got["hemv_hbm_util"] is None


def test_every_reader_reads_the_kinds_facts(bench_dir):
    """Every file under ``layer_metrics/`` called on the facts of a real
    session, every op of the compiled program's entry given 10 ns: no
    reader asks the kind for a key it does not give."""
    _cell, config, traffic = harness.resolve(bench_dir, "t.heig.1x1")
    kind = harness.load_module(bench_dir, "kinds", config["kind"])
    session = kind.setup(config, traffic, jax.devices()[:1], SEED)
    assert harness.judge([session.warm], config["limits"]) == 0
    for key in ("operator", "n", "nb", "grid", "chips", "solve_module",
                "flops_per_solve", "plan_bytes", "plan_parts", "hlo_lines",
                "collectives", "hemv_bytes"):
        assert key in session.facts, key
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
                   "plan_shards", "panel_share", "update_share",
                   "sweep_share", "unscoped_share", "backtransform_share"):
        assert reader in metrics, reader
    assert metrics["backtransform_share"]["value"] > 0.0
    assert metrics["update_share"]["value"] > 0.0
    for reader in ("collective_op_share", "redist_share", "swap_share",
                   "row_permute_share", "panel_gather_share"):
        assert reader not in metrics or metrics[reader]["value"] >= 0.0
