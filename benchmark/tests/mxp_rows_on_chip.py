#!/usr/bin/env python3
"""The readings a ``library_mxp`` cell's limit is set from, on the chip,
at the cell's own size, in one process.

    python3 benchmark/tests/mxp_rows_on_chip.py --workload hplmxp.1x1.b2b \
        [--seeds 12] [--control-seeds 3] [--rows program,f32_update,...] \
        [--n N] [--lu-n 16384] [--first-seed S]

Rows, each one compiled program run on its seeds (generate, ONE timed
solve to ``block_until_ready``, the cell's own check; a warm solve first):

* ``program``: the cell's program as it runs it (``--seeds`` seeds);
* ``unrefined``: control 1, the same with ``max_steps=0`` (no refinement);
* ``high``: control 2, the same with ``precision=Precision.HIGH`` (the
  HIGH side asked for in three bf16 passes: what ``control_on_chip.py``
  runs).  REPORTED, not required to fail: with one right-hand side the
  residual ``b - A x`` and the sweeps' updates are matrix-vector products,
  which the TPU compiler runs on the vector unit in exact float32 whatever
  ``precision`` says, and what is left (the panels) only preconditions;
* ``low_residual``: control 3, the program with the operands of the
  refinement's residual rounded to bfloat16 (``lapack.mixed._residual``
  wrapped here): the high side's one number that decides the answer, in
  the low precision;
* ``f32_update``: the same unpivoted program with the trailing updates in
  float32 at HIGHEST (``lapack.mixed._mixed_solve(low=None)``): the
  high-precision solve the answer is held to, and the S3 row beside
  ``program`` (same operand, same N, same limit);
* ``lu_solve``: ``el.lu_solve`` (partial pivoting) at HIGHEST on the same
  operand at ``--lu-n`` (16384: the pivoted program does not compile at
  32768 on the one-chip host, ROADMAP S1).

Exits 1 unless the configuration's limit on every compared number is at
least three times the largest reading of ``program`` and ``f32_update``
and at least three times under the smallest of ``unrefined`` and of
``low_residual``.  ``--n`` runs the same at a smaller size (the limit is
then only printed).  Not run by the benchmark's own runs.
"""
import argparse
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]

import run as harness  # noqa: E402

SOUND = ("program", "f32_update")
CONTROLS = ("unrefined", "low_residual")
REPORTED = ("high", "lu_solve")


def solvers(nb):
    """row -> f(A, B) -> (X, info or None)."""
    import jax
    import jax.numpy as jnp
    import elemental_tpu as el
    mixed = importlib.import_module("elemental_tpu.lapack.mixed")
    residual = mixed._residual

    def rounded(M):
        return M.with_local(M.local.astype(mixed.LOW).astype(jnp.float32))

    def low_residual(A, B):
        # looked up when the solve is traced, which is inside this call
        mixed._residual = lambda A, X, B, nb, precision: residual(
            rounded(A), rounded(X), B, nb, precision)
        try:
            return el.mixed_solve(A, B, nb=nb)
        finally:
            mixed._residual = residual
    return {
        "low_residual": low_residual,
        "program": lambda A, B: el.mixed_solve(A, B, nb=nb),
        "unrefined": lambda A, B: el.mixed_solve(A, B, nb=nb, max_steps=0),
        "high": lambda A, B: el.mixed_solve(
            A, B, nb=nb, precision=jax.lax.Precision.HIGH),
        "f32_update": lambda A, B: mixed._mixed_solve(
            A, B, nb, None, None, None),
        "lu_solve": lambda A, B: (el.lu_solve(A, B, nb=nb), None),
    }


def row(name, kind, config, traffic, devices, seeds):
    """The row's readings: one compiled program, one solve a seed."""
    import jax
    generate, _solve, check, _grid = kind.programs(config, traffic, devices)
    solve = solvers(config["nb"])[name]
    key = kind._key
    k = key(seeds[0], 0, 0)
    generate = jax.jit(generate).lower(k, k).compile()
    A, B = generate(key(seeds[0], -1, 0), key(seeds[0], -1, 1))
    solve = jax.jit(solve, donate_argnums=0).lower(A, B).compile()
    X, _info = solve(A, B)
    check = jax.jit(check).lower(k, k, X).compile()
    out = []
    for seed in seeds:
        ka, kb = key(seed, 0, 0), key(seed, 0, 1)
        A, B = jax.block_until_ready(generate(ka, kb))
        t0 = time.perf_counter()
        X, info = solve(A, B)
        jax.block_until_ready(X)
        seconds = time.perf_counter() - t0
        numbers = {k_: float(v) for k_, v in check(ka, kb, X).items()}
        if info is not None:
            numbers["refine_steps"] = float(info["steps"])
            numbers["program_backward_error"] = float(info["backward_error"])
        out.append({"solve_s": seconds, **numbers})
        harness.say(row=name, n=config["n"], seed=seed, **out[-1])
    del solve, check, generate
    jax.clear_caches()
    return out


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, default=12)
    parser.add_argument("--control-seeds", type=int, default=3)
    parser.add_argument("--rows", default=",".join(
        SOUND + CONTROLS + REPORTED))
    parser.add_argument("--first-seed", type=int, default=2450000001)
    parser.add_argument("--n", type=int)
    parser.add_argument("--lu-n", type=int, default=16384)
    args = parser.parse_args()

    cell, config, traffic = harness.resolve(BENCH, args.workload)
    if args.n:
        config = {**config, "n": args.n,
                  "nb": min(config["nb"], max(args.n // 8, 1))}
    devices = harness.find_devices(cell["chips"])
    harness.enable_cache()
    kind = harness.load_module(BENCH, "kinds", config["kind"])
    seeds = [args.first_seed + 7919 * k for k in range(args.seeds)]

    got = {}
    for name in args.rows.split(","):
        n = min(args.lu_n, config["n"]) if name == "lu_solve" else config["n"]
        few = seeds if name == "program" else seeds[:args.control_seeds]
        got[name] = row(name, kind, {**config, "n": n}, traffic, devices, few)

    ok = True
    for number, lim in config["limits"].items():
        sound = [r[number] for name in SOUND for r in got.get(name, [])]
        verdict = {"compared": number, "limit": lim["limit"],
                   "sound_largest": max(sound) if sound else None}
        if sound:
            ok &= 3 * max(sound) <= lim["limit"]
        for name in CONTROLS:
            if name in got:
                smallest = min(r[number] for r in got[name])
                verdict[f"{name}_smallest"] = smallest
                ok &= smallest >= 3 * lim["limit"]
        harness.say(**verdict)
    for name, readings in got.items():
        times = sorted(r["solve_s"] for r in readings)
        harness.say(row=name, solves=len(times),
                    solve_s_median=times[len(times) // 2],
                    backward_error_min=min(r["backward_error"]
                                           for r in readings),
                    backward_error_max=max(r["backward_error"]
                                           for r in readings))
    print(json.dumps({"rows_ok": bool(ok), "at_the_cells_size": not args.n}))
    return 0 if ok or args.n else 1


if __name__ == "__main__":
    sys.exit(main())
