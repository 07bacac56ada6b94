"""``BENCHMARK.json`` against the characters and limits the driver
allows, and against the files it names."""
import json
import os
import re

import pytest

import bench_copy
import run as harness

ROOT = os.path.dirname(bench_copy.BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys_and_command(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "benchmark/run.py"]
    assert bench["paths"] == ["benchmark"]
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536


def test_names_units_and_lines(bench):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[group]]
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names), names
    for metric in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(metric["unit"]), metric
        assert metric["better"] in ("lower", "higher")
        assert metric["source"] in SOURCES
    for cell in bench["workloads"]:
        assert NAME.match(cell["config"]) and NAME.match(cell["traffic"])
        assert cell["chips"] in (1, 4)
        assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    texts = ([c["why"] for c in bench["workloads"] + bench["configs"]]
             + [c["source"] for c in bench["configs"]]
             + [m["layer"] for m in bench["per_layer"]])
    for text in texts:
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_end_to_end_metrics(bench):
    by_name = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in by_name and by_name["setup_s"]["bound"] <= 0.25
    for metric in bench["end_to_end"]:
        assert set(metric) <= {"name", "unit", "better", "bound", "source",
                               "workloads"}
        assert 0.01 <= metric["bound"] <= 0.25
        assert metric["source"] in ("host_clock", "device_trace")
        reader = harness.load_module(bench_copy.BENCH, "end_to_end",
                                     metric["name"])
        assert reader.UNIT == metric["unit"]


def test_per_layer_metrics_match_their_reader_files(bench):
    cells = {c["name"] for c in bench["workloads"]}
    end_to_end = {m["name"] for m in bench["end_to_end"]}
    for metric in bench["per_layer"]:
        assert set(metric) <= {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
        reader = harness.load_module(bench_copy.BENCH, "layer_metrics",
                                     metric["name"])
        assert (reader.LAYER, reader.UNIT, reader.MOVES) == (
            metric["layer"], metric["unit"], metric["moves"])
        assert metric["moves"] in end_to_end
        assert set(metric.get("workloads", cells)) <= cells


def test_cells_and_configs_agree_with_their_files(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    used = set()
    pairs = set()
    for cell in bench["workloads"]:
        on_disk, config, _traffic = harness.resolve(bench_copy.BENCH,
                                                    cell["name"])
        assert {k: cell[k] for k in ("config", "traffic", "chips", "why")
                } == on_disk
        entry = configs[cell["config"]]
        assert entry["file"] == f"benchmark/configs/{cell['config']}.json"
        assert sorted(config["reduced"]) == sorted(entry["reduced"])
        assert config["grid"][0] * config["grid"][1] == cell["chips"]
        used.add(cell["config"])
        pairs.add((cell["config"], cell["traffic"]))
    assert used == set(configs)
    assert len(pairs) == len(bench["workloads"])
    four = sum(c["chips"] == 4 for c in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)


def test_file_names_under_paths():
    allowed = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for folder, _dirs, files in os.walk(bench_copy.BENCH):
        if "__pycache__" in folder:
            continue
        for name in files:
            path = os.path.relpath(os.path.join(folder, name), ROOT)
            assert allowed.match(path), path
