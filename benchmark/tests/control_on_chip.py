#!/usr/bin/env python3
"""The lower-precision control, on the chip, at a cell's own size.

    python3 benchmark/tests/control_on_chip.py --workload <cell> \
        [--seeds 12] [--control-seeds 3] [--controls HIGH,DEFAULT] [--n N]

Reads, in one process, the numbers ``correct`` compares: from the
program as the cell runs it (``precision`` not passed: HIGHEST) on
``--seeds`` seeds, and from the program with its own lower-precision
path switched on (``precision=Precision.HIGH``, three bf16 passes: the
nearest precision below float32 at HIGHEST; ``DEFAULT``, one pass, for
scale) on ``--control-seeds`` seeds.  Each reading is one solve of the
cell's compiled program on operands of that seed.

Exits 1 unless, for every compared number, the control's smallest
reading is at least three times the sound runs' largest and the
configuration's limit lies between the two.  ``--n`` runs the same at a
smaller size (the limits are then only printed: they were set at the
cell's size).  Not run by the benchmark's own runs.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]

import run as harness  # noqa: E402


def readings(kind, config, traffic, devices, seeds, precision):
    import jax
    session = kind.Session(config, traffic, devices, seeds[0],
                           precision=precision)
    out = []
    for seed in seeds:
        session.seed = seed
        _seconds, numbers = harness.timed_solve(session, 0)
        out.append(numbers)
        harness.say(precision=str(precision), seed=seed, **numbers)
    del session
    jax.clear_caches()
    return out


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, default=12)
    parser.add_argument("--control-seeds", type=int, default=3)
    parser.add_argument("--controls", default="HIGH")
    parser.add_argument("--first-seed", type=int, default=2200000001)
    parser.add_argument("--n", type=int)
    args = parser.parse_args()

    cell, config, traffic = harness.resolve(BENCH, args.workload)
    if args.n:
        config = {**config, "n": args.n, "nb": min(config["nb"], args.n)}
    devices = harness.find_devices(cell["chips"])
    import jax
    harness.enable_cache()
    kind = harness.load_module(BENCH, "kinds", config["kind"])
    seeds = [args.first_seed + 7919 * k for k in range(args.seeds)]

    sound = readings(kind, config, traffic, devices, seeds, None)
    ok = True
    for name in args.controls.split(","):
        control = readings(kind, config, traffic, devices,
                           seeds[:args.control_seeds],
                           getattr(jax.lax.Precision, name))
        for number, lim in config["limits"].items():
            largest = max(r[number] for r in sound)
            smallest = min(r[number] for r in control)
            fails = all(not r[number] <= lim["limit"] for r in control)
            verdict = {
                "compared": number, "control": name,
                "sound_largest": largest, "control_smallest": smallest,
                "ratio": smallest / largest, "limit": lim["limit"],
                "sound_passes": largest <= lim["limit"],
                "control_fails": fails}
            harness.say(**verdict)
            if name == "HIGH" and not args.n:
                ok &= (smallest >= 3 * largest and fails
                       and largest <= lim["limit"])
    print(json.dumps({"control_ok": bool(ok)}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
