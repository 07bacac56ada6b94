"""The harness end to end on virtual CPU devices at N = 256: the public
drivers against float64 numpy on the same generated matrices, a cell, a
kind and a per-layer metric added as new files, and a broken timed path
coming out as not correct."""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import bench_copy
import reference
import run as harness
import xplane


@pytest.fixture
def bench_dir(tmp_path):
    return bench_copy.make(tmp_path / "benchmark")


def run_cell(bench_dir, cell, chips, seconds="0.2", seed="2147483999"):
    return harness.main(["--workload", cell, "--seed", seed, "--seconds",
                         seconds, "--trace", "0"], bench_dir=bench_dir,
                        devices=jax.devices()[:chips])


@pytest.mark.parametrize("cell,chips", [("t.hpd.1x1", 1), ("t.lu.1x1", 1),
                                        ("t.hpd.2x2", 4)])
def test_run_is_correct_and_reports_every_end_to_end_metric(
        bench_dir, cell, chips, capsys):
    line = run_cell(bench_dir, cell, chips)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["metrics"]) == {"solve_s", "plan_gb", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["count"] == chips
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == line


@pytest.mark.parametrize("cell,chips,tol", [
    ("t.hpd.1x1", 1, 1e-5), ("t.lu.1x1", 1, 1e-3), ("t.hpd.2x2", 4, 1e-5)])
def test_solution_matches_float64_numpy(bench_dir, cell, chips, tol):
    """X of the timed path against numpy.linalg.solve in float64 on the
    same generated A and B (forward error; float32 solve, so the bound
    is eps times the operand's condition with room)."""
    import elemental_tpu as el
    _cell, config, traffic = harness.resolve(bench_dir, cell)
    kind = harness.load_module(bench_dir, "kinds", config["kind"])
    seed = 7
    session = kind.setup(config, traffic, jax.devices()[:chips], seed)
    X = np.asarray(el.to_global(session.solve(session.prepare(3))),
                   np.float64)
    n, nrhs = config["n"], traffic["nrhs"]
    keys = [np.uint32(reference.operand_key(seed, 3, w)) for w in (0, 1)]
    A = np.asarray(reference.plain_block(
        reference.ENTRIES[config["operand"]](n, keys[0]), 0, n, n),
        np.float64)
    B = np.asarray(reference.plain_block(
        reference.ENTRIES["uniform_pm1"](n, keys[1]), 0, n, nrhs),
        np.float64)
    want = np.linalg.solve(A, B)
    assert np.linalg.norm(X - want) / np.linalg.norm(want) < tol


def test_broken_timed_path_is_not_correct(bench_dir):
    """A kind, added as a new file, whose solve alters the answer where
    it is produced: the rest of a run goes through, ``correct`` is false
    and every solve counts as failed."""
    with open(os.path.join(bench_dir, "kinds", "broken_solve.py"), "w") as f:
        f.write(
            "import run as harness\n"
            "def setup(config, traffic, devices, seed):\n"
            "    good = harness.load_module(%r, 'kinds', 'library_solve')\n"
            "    session = good.setup(config, traffic, devices, seed)\n"
            "    solve = session.solve\n"
            "    def altered(operands):\n"
            "        X = solve(operands)\n"
            "        return X.with_local(X.local * 1.001)\n"
            "    session.solve = altered\n"
            "    return session\n" % bench_dir)
    config = {**bench_copy.CONFIGS["t-hpd-1x1"], "kind": "broken_solve"}
    bench_copy.write_json(os.path.join(bench_dir, "configs",
                                       "t-broken.json"), config)
    bench_copy.write_json(
        os.path.join(bench_dir, "workloads", "t.broken.json"),
        {"config": "t-broken", "traffic": "b2b.rhs8", "chips": 1,
         "why": "test"})
    line = run_cell(bench_dir, "t.broken", 1)
    assert line["correct"] is False
    assert line["failed"] == line["attempted"] >= 1


def hand_made_planes():
    """Two solves of 100 ns on one device, 10 ns idle in each."""
    ops = [("fusion.1 f32[8,8]", 1000.0, 60.0),
           ("all-gather.2 f32[8,8]", 1070.0, 30.0),
           ("fusion.1 f32[8,8]", 2000.0, 90.0),
           ("copy.9 f32[8]", 5000.0, 10.0)]              # the check's
    modules = [("jit_bench_solve(1)", 1000.0, 100.0),
               ("jit_bench_solve(1)", 2000.0, 100.0),
               ("jit_check(2)", 5000.0, 10.0)]
    return {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": modules},
            "/host:CPU": {"python3": [("x", 0.0, 1.0)]}}


def test_new_layer_metric_is_a_new_file(bench_dir):
    """Every reader in the folder is read; one that returns None is left
    out (collective_op_share on one chip); a throw-away one is found."""
    with open(os.path.join(bench_dir, "layer_metrics",
                           "timed_solves.py"), "w") as f:
        f.write("LAYER, UNIT, MOVES = 'Device', 'count', 'solve_s'\n"
                "def read(trace, run):\n"
                "    return trace['devices'][0]['n_timed']\n")
    trace = xplane.reduce_trace(hand_made_planes(), "jit_bench_solve")
    facts = {"chips": 1, "flops_per_solve": 1.97e5, "hlo_lines": 1234}
    run = {"facts": facts, "peak": {"bf16_flops_per_s": 197e12}}
    got = harness.read_metrics(bench_dir, "layer_metrics", trace, run)
    assert set(got) == {"device_idle_share", "flops_util", "hlo_lines",
                        "timed_solves"}
    assert got["timed_solves"] == {"value": 2, "unit": "count"}
    assert got["device_idle_share"]["value"] == pytest.approx(10.0)
    assert got["hlo_lines"]["value"] == 1234
    # 1.97e5 flops in 90 ns busy over 197e12: 1.97e5 / 90e-9 / 197e12
    assert got["flops_util"]["value"] == pytest.approx(100 / 90)
    run["facts"] = {**facts, "chips": 4}
    got = harness.read_metrics(bench_dir, "layer_metrics", trace, run)
    assert got["collective_op_share"]["value"] == pytest.approx(15.0)


def test_run_refuses_to_measure_without_a_tpu():
    done = subprocess.run(
        [sys.executable, os.path.join(bench_copy.BENCH, "run.py"),
         "--workload", "hpd32k.1x1.b2b", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode != 0
    assert "needs a TPU" in done.stderr
    assert '"correct"' not in done.stdout
