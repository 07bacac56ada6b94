#!/usr/bin/env python3
"""The traced window of a cell, op by op, to read by hand: what
``run.py --trace 1`` prints (its own ``main``, its own last line), then
the longest ops of one device with the class and the scope each is
booked under, and the longest unscoped ones.

    python3 benchmark/tests/list_trace.py --workload <cell> --seed <n> \
        [--top 60] [--out file]

Self time (``scopes.self_times``) a solve, summed per instruction.  Not
run by the benchmark's own runs; needs the cell's chips.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]

import run as harness  # noqa: E402
import scopes  # noqa: E402


def listing(trace, module, top=60):
    """``{"device", "longest", "unscoped"}``: per instruction of the first
    device the events and milliseconds a solve, longest first."""
    device = min(trace["devices"])
    d = trace["devices"][device]
    per = {}
    for name, self_ns in scopes.self_times(d["timed_ops"]):
        instruction = scopes.event_instruction(name)
        cls, detail = module.instruction_class(instruction)
        row = per.setdefault((instruction, name.partition(" ")[2], cls,
                              detail), [0, 0.0])
        row[0] += 1
        row[1] += self_ns
    rows = [{"op": op, "shape": shape, "class": cls, "detail": detail,
             "events_a_solve": n / d["n_timed"],
             "ms_a_solve": ns / d["n_timed"] * 1e-6}
            for (op, shape, cls, detail), (n, ns) in per.items()]
    rows.sort(key=lambda r: -r["ms_a_solve"])
    return {"device": device, "longest": rows[:top],
            "unscoped": [r for r in rows if r["class"] == scopes.UNSCOPED
                         ][:top]}


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", required=True)
    parser.add_argument("--top", type=int, default=60)
    parser.add_argument("--out")
    args = parser.parse_args()

    kept = {}                      # the session keeps its executable alive
    run_traced = harness.run_traced

    def keeping(session):
        result = run_traced(session)
        kept["session"], kept["trace"] = session, result[2]
        return result
    harness.run_traced = keeping
    line = harness.main(["--workload", args.workload, "--seed", args.seed,
                         "--seconds", "10", "--trace", "1"])

    module = scopes._module_of(scopes.module_texts(
        kept["session"].facts["solve_module"]), kept["trace"])
    text = json.dumps({"listing": listing(kept["trace"], module, args.top)})
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text + "\n")
    else:
        print(text)
    print(json.dumps(line))        # run.py's own last line, last again


if __name__ == "__main__":
    main()
