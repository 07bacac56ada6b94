"""A throw-away copy of the benchmark's data and readers, to which a
test adds cells, configurations, kinds and metrics as NEW files."""
import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)

#: n = 256 twins of the three configurations
SMALL = {"kind": "library_solve", "dtype": "float32", "n": 256, "nb": 64}
CONFIGS = {
    "t-hpd-1x1": {**SMALL, "operator": "hpd_solve", "operand": "hpd_shifted",
                  "grid": [1, 1],
                  "limits": {"backward_error": {"limit": 1e-6}}},
    "t-hpd-2x2": {**SMALL, "operator": "hpd_solve", "operand": "hpd_shifted",
                  "grid": [2, 2],
                  "limits": {"backward_error": {"limit": 1e-6}}},
    "t-lu-1x1": {**SMALL, "operator": "lu_solve", "operand": "uniform_pm1",
                 "grid": [1, 1],
                 "limits": {"backward_error": {"limit": 1e-5}},
                 "printed_only": {"hpl_scaled": 16.0}},
}
CELLS = {
    "t.hpd.1x1": {"config": "t-hpd-1x1", "traffic": "b2b.rhs8", "chips": 1},
    "t.hpd.2x2": {"config": "t-hpd-2x2", "traffic": "b2b.rhs8", "chips": 4},
    "t.lu.1x1": {"config": "t-lu-1x1", "traffic": "b2b.rhs1", "chips": 1},
}


def write_json(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f)


def make(dst):
    """Copy the benchmark (without its tests) to ``dst`` and add the small
    cells and a peak for the CPU 'device', each as a new file."""
    shutil.copytree(BENCH, dst, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    with open(os.path.join(dst, "peaks.json")) as f:
        peaks = json.load(f)
    peaks["cpu"] = {"bf16_flops_per_s": 1e12, "source": "made up, for tests"}
    write_json(os.path.join(dst, "peaks.json"), peaks)
    for name, config in CONFIGS.items():
        write_json(os.path.join(dst, "configs", name + ".json"), config)
    for name, cell in CELLS.items():
        write_json(os.path.join(dst, "workloads", name + ".json"),
                   {**cell, "why": "test"})
    return str(dst)
