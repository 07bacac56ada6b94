#!/usr/bin/env python3
"""Look at one real trace by hand: planes, lines, event counts and the
first names of a traced window of a cell, as the harness records it.

    python3 benchmark/tests/dump_trace.py --workload <cell> [--out file]
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]

import run as harness  # noqa: E402
import xplane  # noqa: E402


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--out")
    args = parser.parse_args()
    cell, config, traffic = harness.resolve(BENCH, args.workload)
    devices = harness.find_devices(cell["chips"])
    harness.enable_cache()
    kind = harness.load_module(BENCH, "kinds", config["kind"])
    session = kind.setup(config, traffic, devices, args.seed)
    _s, _c, trace, planes = harness.run_traced(session)
    out = open(args.out, "w") if args.out else sys.stdout
    for plane, lines in planes.items():
        for line, events in lines.items():
            print(json.dumps({
                "plane": plane, "line": line, "events": len(events),
                "first": [list(e) for e in events[:6]],
                "top": xplane.top_names(events, 12)}), file=out)
    for dev, d in trace["devices"].items():
        print(json.dumps({"device": dev, **{
            k: d[k] for k in ("n_timed", "timed_s", "timed_busy_s",
                              "busy_s", "window_s")},
            "windows": d["windows"]}), file=out)


if __name__ == "__main__":
    main()
