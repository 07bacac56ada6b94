"""tools/bench_diff.py regression gating (ISSUE 5): a synthetic >= 10%
cholesky TFLOP/s drop must flag (exit non-zero); in-tolerance runs pass.
The tool is stdlib-only, loaded straight from tools/."""
import importlib.util
import json
import os

import pytest

_TOOL = os.path.join(os.path.dirname(__file__), os.pardir, os.pardir,
                     "tools", "bench_diff.py")


@pytest.fixture(scope="module")
def bd():
    spec = importlib.util.spec_from_file_location("bench_diff", _TOOL)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _write(tmp_path, name, value, vs_baseline, lu_value=5.0,
           lu_vs_baseline=0.35, wrapped=True):
    doc = {"metric": "cholesky_n32768_tflops_per_chip", "value": value,
           "unit": "TFLOP/s", "vs_baseline": vs_baseline,
           "lu_value": lu_value, "lu_vs_baseline": lu_vs_baseline}
    if wrapped:
        doc = {"n": 1, "cmd": "python bench.py", "rc": 0, "parsed": doc}
    path = tmp_path / name
    path.write_text(json.dumps(doc))
    return str(path)


def test_flags_synthetic_cholesky_regression(bd, tmp_path, capsys):
    """>= 10% drop in cholesky TFLOP/s (and its roofline-normalized
    ratio) vs the trajectory best -> exit 1, named in the output."""
    _write(tmp_path, "BENCH_r01.json", value=10.0, vs_baseline=0.70)
    cur = _write(tmp_path, "BENCH_r02.json", value=8.9, vs_baseline=0.62)
    assert bd.main(["--check", cur]) == 1
    out = capsys.readouterr().out
    assert "vs_baseline" in out and "REGRESSION" in out
    # the raw-TFLOP/s metric gates the same synthetic drop explicitly
    assert bd.main(["--check", cur, "--metric", "value"]) == 1
    out = capsys.readouterr().out
    assert "value" in out and "REGRESSION" in out


def test_within_threshold_passes(bd, tmp_path, capsys):
    _write(tmp_path, "BENCH_r01.json", value=10.0, vs_baseline=0.70)
    cur = _write(tmp_path, "BENCH_r02.json", value=9.5, vs_baseline=0.665)
    assert bd.main(["--check", cur]) == 0
    assert "REGRESSION" not in capsys.readouterr().out


def test_gate_compares_against_trajectory_best(bd, tmp_path):
    """A slow decay cannot ratchet the bar down: the gate uses the BEST
    baseline in the trajectory, not the latest."""
    _write(tmp_path, "BENCH_r01.json", value=10.0, vs_baseline=0.75)
    _write(tmp_path, "BENCH_r02.json", value=9.3, vs_baseline=0.70)
    # within 10% of r02, but 10.7% below r01's best
    cur = _write(tmp_path, "BENCH_r03.json", value=8.93, vs_baseline=0.67)
    assert bd.main(["--check", cur]) == 1


def test_threshold_flags_global_and_per_metric(bd, tmp_path):
    _write(tmp_path, "BENCH_r01.json", value=10.0, vs_baseline=0.70,
           lu_vs_baseline=0.40)
    cur = _write(tmp_path, "BENCH_r02.json", value=8.9, vs_baseline=0.62,
                 lu_vs_baseline=0.39)
    # loosening the global threshold passes the same drop
    assert bd.main(["--check", cur, "--threshold", "0.20"]) == 0
    # per-metric override: only lu gets the tight threshold -> its 2.5%
    # drop passes, cholesky's 11% drop still fails under the default
    assert bd.main(["--check", cur,
                    "--threshold", "lu_vs_baseline=0.01"]) == 1
    assert bd.main(["--check", cur, "--threshold", "0.20",
                    "--threshold", "lu_vs_baseline=0.01"]) == 1


def test_explicit_current_vs_baselines(bd, tmp_path):
    base = _write(tmp_path, "BENCH_r01.json", value=10.0, vs_baseline=0.70)
    cur = _write(tmp_path, "current.json", value=6.0, vs_baseline=0.45,
                 wrapped=False)                 # raw bench.py line form
    assert bd.main([cur, base]) == 1
    assert bd.main([base, base]) == 0


def test_no_baselines_or_metrics_is_not_an_error(bd, tmp_path, capsys):
    cur = _write(tmp_path, "BENCH_r01.json", value=10.0, vs_baseline=0.70)
    assert bd.main(["--check", cur]) == 0       # nothing earlier to gate
    assert "no baselines" in capsys.readouterr().out
    _write(tmp_path, "BENCH_r00.json", value=1.0, vs_baseline=0.1)
    # metrics absent on both sides are skipped with a note, not a crash
    assert bd.main(["--check", cur, "--metric", "does_not_exist"]) == 0
    assert "no comparable metrics" in capsys.readouterr().out


def test_obs_wire_bytes_key_accepted_not_gated(bd, tmp_path, capsys):
    """ISSUE 8: a current doc carrying the new obs.redist_wire_bytes
    total (and a comm_precision tuner provenance field) passes the gate
    against baselines that predate the key -- surfaced as an
    informational line, never a regression (the rename guard stays
    false-positive-free)."""
    _write(tmp_path, "BENCH_r01.json", value=10.0, vs_baseline=0.70)
    doc = {"metric": "cholesky_n32768_tflops_per_chip", "value": 10.0,
           "unit": "TFLOP/s", "vs_baseline": 0.70, "lu_value": 5.0,
           "lu_vs_baseline": 0.35,
           "tuner": {"ran_with": {"nb": 2048, "comm_precision": None},
                     "lu": {"config": {"comm_precision": "bf16"},
                            "source": "cost_model"}},
           "obs": {"schema": "obs_bench/v1", "redist_bytes": 1000,
                   "redist_wire_bytes": 500}}
    path = tmp_path / "BENCH_r02.json"
    path.write_text(json.dumps({"parsed": doc}))
    assert bd.main(["--check", str(path)]) == 0
    out = capsys.readouterr().out
    assert "redist_wire_bytes: 500" in out and "2.00x" in out
    assert "REGRESSION" not in out
