#!/usr/bin/env python3
"""Proof that the solver path starts, runs and answers right on the chip.

    python chip_smoke.py            # one TPU chip: every phase below
    python chip_smoke.py --chips 4  # the four-chip host: the 2x2 phase only

One process holds the chip and runs every phase through the entry points
a user calls (``el.Grid()``, the ``el.*`` drivers, ``el.serve``), with no
performance knob a user would not pass.  Every phase prints one JSON line
marked ``"smoke": true`` -- sizes, residuals, compile and run seconds,
device memory -- and NONE of them is a measurement: no warm-up, one
reading, compilation wherever it fell.  A phase that fails raises; the
script then prints ``{"ok": false, ...}`` and exits non-zero.  The last
line of a passing run is exactly::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

There is no CPU branch: where JAX finds no TPU the script fails at once.
``tests/test_chip_smoke.py`` rehearses the phase functions at small sizes
on the virtual CPU mesh by calling them directly.
"""
import argparse
import json
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

import elemental_tpu as el
from elemental_tpu.core.compile_cache import enable_compile_cache

HI = jax.lax.Precision.HIGHEST

#: the repo's documented residual class (.claude/skills/verify): 50 eps n
TOL_FACTOR = 50.0


def tol(n, dtype=jnp.float32):
    return TOL_FACTOR * n * float(jnp.finfo(dtype).eps)


def emit(phase, **fields):
    print(json.dumps({"smoke": True, "note": "smoke line, not a measurement",
                      "phase": phase, **fields}), flush=True)


def peak_bytes(dev):
    return (dev.memory_stats() or {}).get("peak_bytes_in_use")


# ---------------------------------------------------------------------
# operands: made on the device from a seed, entry by entry from the
# GLOBAL index, so the same seed gives the same matrix on any grid
# ---------------------------------------------------------------------

def _hash_pm1(i, j, seed):
    """uint32 mix of (i, j, seed) -> float32 in [-1, 1)."""
    x = (i.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
         ^ j.astype(jnp.uint32) * jnp.uint32(0x85EBCA77)
         ^ jnp.uint32((seed * 0xC2B2AE3D + 0x27D4EB2F) & 0xFFFFFFFF))
    x = (x ^ (x >> 15)) * jnp.uint32(0x2C1B3C6D)
    x = (x ^ (x >> 12)) * jnp.uint32(0x297A2D39)
    x = x ^ (x >> 15)
    return (x >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -23) - 1.0


def gen_general(m, n, grid, seed, dtype=jnp.float32):
    """Dense (m, n) with entries uniform in [-1, 1)."""
    def f(i, j):
        re = _hash_pm1(i, j, seed)
        if jnp.issubdtype(dtype, jnp.complexfloating):
            return (re + 1j * _hash_pm1(i, j, seed + 1)).astype(dtype)
        return re.astype(dtype)
    return el.index_dependent_fill(el.matrices.zeros(m, n, grid, dtype), f)


def gen_hpd(n, grid, seed, dtype=jnp.float32):
    """Hermitian, off-diagonal entries in the unit disc, diagonal 2n:
    positive definite by Gershgorin."""
    def f(i, j):
        lo, hi = jnp.minimum(i, j), jnp.maximum(i, j)
        v = _hash_pm1(lo, hi, seed).astype(dtype)
        if jnp.issubdtype(dtype, jnp.complexfloating):
            im = _hash_pm1(lo, hi, seed + 1) * jnp.sign(i - j)
            v = (v + 1j * im.astype(v.real.dtype)).astype(dtype)
        return jnp.where(i == j, jnp.asarray(2.0 * n, dtype), v)
    return el.index_dependent_fill(el.matrices.zeros(n, n, grid, dtype), f)


def backward_error(A, X, B):
    """||B - A X||_F / (||A||_F ||X||_F + ||B||_F), on the device, through
    the library's own gemm and norms (the certificate's formula,
    resilience/certify.py)."""
    R = el.gemm(A, X, precision=HI)
    R = R.with_local(B.local - R.local)
    return el.frobenius_norm(R) / (
        el.frobenius_norm(A) * el.frobenius_norm(X) + el.frobenius_norm(B))


_COLLECTIVES = ("all-gather", "all-reduce", "all-to-all",
                "collective-permute", "reduce-scatter")


def _timed_compile(fn, *args, donate=()):
    """(traced, compiled, seconds) of one trace + lower + compile."""
    t0 = time.perf_counter()
    traced = jax.jit(fn, donate_argnums=donate).trace(*args)
    compiled = traced.lower().compile()
    return traced, compiled, time.perf_counter() - t0


def _planned_bytes(traced, compiled, grid):
    """Per-device bytes two planners give the program, to set beside what
    the device reports: the compiler's own (arguments + outputs + temps,
    less aliases) and the jaxpr liveness walk's (analysis/memory.py)."""
    from elemental_tpu.analysis.memory import analyze_jaxpr
    m = compiled.memory_analysis()
    return {"compiler": int(m.argument_size_in_bytes + m.output_size_in_bytes
                            + m.temp_size_in_bytes - m.alias_size_in_bytes),
            "liveness_walk": int(analyze_jaxpr(traced.jaxpr,
                                               grid.size).peak_bytes)}


def _timed_run(compiled, *args):
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    return out, time.perf_counter() - t0


def _solve_line(phase, name, solve, gen_a, shape_a, nrhs, grid, seed, n_tol,
                **extra):
    """Generate (A, B) on the device, run the jitted ``solve`` with A
    donated, regenerate A and check the backward error on the device."""
    m, n = shape_a
    dev = grid.devices[0]
    dtype = extra.pop("dtype", jnp.float32)
    gen = jax.jit(lambda: (gen_a(), gen_general(m, nrhs, grid, seed + 7,
                                                dtype)))
    A, B = jax.block_until_ready(gen())
    # where A really is: code that has only met virtual devices may put
    # everything on the first
    placement = [{"device": s.device.id, "shard_bytes": int(s.data.nbytes),
                  "bytes_in_use": (s.device.memory_stats() or {}
                                   ).get("bytes_in_use")}
                 for s in A.local.addressable_shards]
    traced, compiled, t_compile = _timed_compile(solve, A, B, donate=0)
    planned = _planned_bytes(traced, compiled, grid)
    del traced
    X, t_run = _timed_run(compiled, A, B)
    del A
    if X.gshape[0] != n:                  # least squares: X is (n, nrhs)
        raise AssertionError(f"{name}: solution shape {X.gshape}")
    if m == n:
        err = float(jax.jit(
            lambda X, B: backward_error(gen_a(), X, B))(X, B))
    else:
        # least squares: the residual is orthogonal to range(A);
        # ||A^H (B - A X)||_F / (||A||_F (||A||_F ||X||_F + ||B||_F))
        def normal_resid(X, B):
            A = gen_a()
            R = el.gemm(A, X, precision=HI)
            R = R.with_local(B.local - R.local)
            G = el.gemm(A, R, orient_a="C", precision=HI)
            na = el.frobenius_norm(A)
            return el.frobenius_norm(G) / (na * (
                na * el.frobenius_norm(X) + el.frobenius_norm(B)))
        err = float(jax.jit(normal_resid)(X, B))
    bound = tol(n_tol, jnp.float32)
    text = compiled.as_text()
    line = dict(op=name, shape=[m, n], nrhs=nrhs, dtype=jnp.dtype(dtype).name,
                grid=[grid.height, grid.width], backward_error=err,
                tol=bound, compile_s=round(t_compile, 2),
                run_s=round(t_run, 3), planned_bytes=planned,
                peak_bytes_in_use=peak_bytes(dev), placement=placement,
                collectives={c: text.count(f" {c}(") + text.count(
                    f" {c}-start(") for c in _COLLECTIVES}, **extra)
    emit(phase, **line)
    if not err <= bound:                  # also catches NaN
        raise AssertionError(f"{name}: backward error {err:.3e} > {bound:.3e}")
    return X, line


# ---------------------------------------------------------------------
# phase: the library at real size
# ---------------------------------------------------------------------

#: the algorithmic blocksize the real-size solves are called with: the
#: one the benchmark's cells pass.  (The library default, 128,
#: is sized for the test meshes; at N = 32768 it unrolls 256 panel steps
#: into one program, which alone takes minutes to compile.)
NB = 2048

#: ``lu_solve`` runs at half the N of ``hpd_solve``, and not for the
#: chip's memory: the LU program unrolls one loop per 64 columns (the
#: panel chunk ladder), 178,000 lines of HLO at N = 32768, and COMPILING
#: it took the host past the one-chip machine's 40 GiB (the process was
#: killed) and eight minutes of a twenty-minute script.  At N = 16384 it
#: is 89,000 lines, 19 GB of host memory and under four minutes.
N_LU = 16384


def phase_library(grid, n=32768, n_lu=N_LU, nrhs=8, ls_shape=(65536, 512),
                  nb=NB, seed=0):
    _solve_line("library", "hpd_solve",
                lambda A, B: el.hpd_solve(A, B, nb=nb),
                lambda: gen_hpd(n, grid, seed), (n, n), nrhs, grid, seed, n,
                nb=nb)
    _solve_line("library", "lu_solve", lambda A, B: el.lu_solve(A, B, nb=nb),
                lambda: gen_general(n_lu, n_lu, grid, seed + 1),
                (n_lu, n_lu), nrhs, grid, seed + 1, n_lu, nb=nb)
    m, k = ls_shape
    _solve_line("library", "least_squares",
                lambda A, B: el.least_squares(A, B),
                lambda: gen_general(m, k, grid, seed + 2), (m, k), nrhs,
                grid, seed + 2, m)


# ---------------------------------------------------------------------
# phase: the fused panel kernels beside their XLA twins
# ---------------------------------------------------------------------

def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def phase_kernels(grid, n=2048, nb=256, seed=10, expect_custom_call=True):
    """``panel_impl='pallas'`` beside ``'xla'`` on the same input.  On a
    TPU the compiled program must CONTAIN the kernel (``tpu_custom_call``):
    an interpreted run, or a gate that sent every panel to XLA, fails."""
    bound = tol(n)
    A = jax.block_until_ready(jax.jit(
        lambda: gen_general(n, n, grid, seed))())
    S = jax.block_until_ready(jax.jit(lambda: gen_hpd(n, grid, seed))())
    Ag, Sg = el.to_global(A), el.to_global(S)

    def twins(driver, arg):
        """``driver`` under both panel implementations; global arrays."""
        def is_dm(x):
            return isinstance(x, el.DistMatrix)
        outs, secs, has = {}, {}, {}
        for impl in ("pallas", "xla"):
            _, compiled, tc = _timed_compile(
                lambda a: jax.tree.map(
                    lambda x: el.to_global(x) if is_dm(x) else x,
                    driver(a, nb=nb, panel_impl=impl), is_leaf=is_dm), arg)
            has[impl] = "tpu_custom_call" in compiled.as_text()
            outs[impl], tr = _timed_run(compiled, arg)
            secs[impl] = {"compile_s": round(tc, 2), "run_s": round(tr, 3)}
        if expect_custom_call and not has["pallas"]:
            raise AssertionError(
                "panel_impl='pallas' compiled to a program without a "
                "tpu_custom_call: the kernel did not run compiled")
        return outs, secs, has["pallas"]

    def check(name, errs, secs, has, **extra):
        emit("kernels", op=name, n=n, nb=nb, tol=bound,
             tpu_custom_call=has, seconds=secs, **errs, **extra)
        bad = {k: v for k, v in errs.items() if not v <= bound}
        if bad:
            raise AssertionError(f"{name} twins: {bad} > {bound:.3e}")

    # LU: P A = L U for each twin (the blocked kernel promises the
    # residual, not the XLA ladder's pivots -- their agreement is printed)
    outs, secs, has = twins(el.lu, A)
    errs = {}
    for impl, (LU, perm) in outs.items():
        L = jnp.tril(LU, -1) + jnp.eye(n, dtype=LU.dtype)
        errs[f"resid_{impl}"] = _rel(
            jnp.matmul(L, jnp.triu(LU), precision=HI), Ag[perm])
    same = float(jnp.mean(outs["pallas"][1] == outs["xla"][1]))
    check("lu", errs, secs, has, pivots_equal_frac=same)

    # Cholesky: the factor is unique, so the twins agree entry for entry
    outs, secs, has = twins(el.cholesky, S)
    errs = {f"resid_{impl}": _rel(jnp.matmul(L, L.T, precision=HI), Sg)
            for impl, L in outs.items()}
    errs["factor_diff"] = _rel(outs["pallas"], outs["xla"])
    check("cholesky", errs, secs, has)

    # QR: R^T R = A^T A for each twin; same larfg sign convention, so the
    # packed factors (R above, reflectors below) and tau agree too
    outs, secs, has = twins(el.qr, A)
    AtA = jnp.matmul(Ag.T, Ag, precision=HI)
    errs = {}
    for impl, (QR, _tau) in outs.items():
        R = jnp.triu(QR)
        errs[f"resid_{impl}"] = _rel(jnp.matmul(R.T, R, precision=HI), AtA)
    errs["r_diff"] = _rel(jnp.triu(outs["pallas"][0]),
                          jnp.triu(outs["xla"][0]))
    check("qr", errs, secs, has)


# ---------------------------------------------------------------------
# phase: the serving front door
# ---------------------------------------------------------------------

def _requests(sizes, seed):
    """One float32 problem per (size, op) bucket: lu / hpd / lstsq."""
    rng = np.random.default_rng(seed)
    out = []
    for i, n in enumerate(sizes):
        for op in ("lu", "hpd", "lstsq"):
            nrhs = 1 + (i % 3)
            if op == "lstsq":
                A = rng.standard_normal((2 * n, n))
                B = rng.standard_normal((2 * n, nrhs))
            else:
                A = rng.standard_normal((n, n))
                if op == "hpd":
                    A = A @ A.T / n + np.eye(n)
                B = rng.standard_normal((n, nrhs))
            out.append((op, A.astype(np.float32), B.astype(np.float32)))
    return out


def phase_serving(devices, sizes=(96, 200, 384, 512, 768, 1024), seed=20):
    """One ``SolverFleet`` of one grid answers a few dozen requests in
    three passes over the same buckets:

    * ``cold`` and ``again``: one request at a time, each awaited, so
      every batch has the same geometry both times -- the second pass
      must compile nothing;
    * ``burst``: all at once, twice over, so batches overlap in the
      pipelined worker (the donated-buffer path that is off on the CPU).
      How the burst splits into batches depends on timing, so its
      compiles (wider batch variants) are printed, not asserted.

    Every ``serve_result/v1`` is ``ok`` (the host float64 certificate
    holds each to ``default_tol(n, float32)``), and shutdown leaves no
    worker thread."""
    from elemental_tpu.obs import metrics
    from elemental_tpu.serve.async_front import donation_safe
    from elemental_tpu.serve.fleet import SolverFleet

    def compiles(reg):
        return int(sum(v for (_, labels), v in
                       reg.counters("serve_exec_cache_events").items()
                       if dict(labels).get("event") == "compile"))

    fleet = SolverFleet(list(devices), grids=1)
    passes = {}
    try:
        for p, name in enumerate(("cold", "again", "burst")):
            reqs = _requests(sizes, seed + p)
            with metrics.scoped() as reg:
                t0 = time.perf_counter()
                if name == "burst":
                    reqs = reqs + _requests(sizes, seed + p + 1)
                    futs = [fleet.submit(op, A, B) for op, A, B in reqs]
                    outs = [f.result(timeout=900.0) for f in futs]
                else:
                    outs = [fleet.submit(op, A, B).result(timeout=900.0)
                            for op, A, B in reqs]
                wall = time.perf_counter() - t0
                n_compiles = compiles(reg)
            docs = [d for _, d in outs]
            bad = [(d.get("op"), d.get("n"), d.get("status"),
                    d.get("reason")) for d in docs if d.get("status") != "ok"]
            passes[name] = dict(
                requests=len(reqs), ok=len(docs) - len(bad),
                exec_compiles=n_compiles,
                worst_residual_over_tol=max(
                    (d["residual"] / d["tol"] for d in docs
                     if d.get("status") == "ok"), default=None),
                rungs=sorted({str(d.get("rung")) for d in docs}),
                wall_s=round(wall, 2))
            if bad:
                raise AssertionError(f"serving {name}: not ok: {bad[:6]}")
            for (op, A, B), (x, d) in zip(reqs, outs):
                if x is None or x.shape != (A.shape[1], B.shape[1]) \
                        or not np.all(np.isfinite(x)):
                    raise AssertionError(f"serving: bad solution for {op} "
                                         f"n={A.shape[1]}")
    finally:
        fleet.shutdown(drain=True)
    leaked = [t.name for t in threading.enumerate()
              if t.name.startswith("elemental-serve-worker") and t.is_alive()]
    emit("serving", front="SolverFleet(grids=1)", sizes=list(sizes),
         donate=donation_safe(), passes=passes, leaked_threads=leaked,
         peak_bytes_in_use=peak_bytes(devices[0]))
    if passes["again"]["exec_compiles"] != 0:
        raise AssertionError(
            "serving: the second pass over the same buckets compiled "
            f"{passes['again']['exec_compiles']}")
    if leaked:
        raise AssertionError(f"serving: worker threads left: {leaked}")


# ---------------------------------------------------------------------
# phase: one small complex solve
# ---------------------------------------------------------------------

def phase_complex(grid, n=1024, nrhs=4, seed=30):
    _solve_line("complex", "hpd_solve", lambda A, B: el.hpd_solve(A, B),
                lambda: gen_hpd(n, grid, seed, jnp.complex64), (n, n), nrhs,
                grid, seed, n, dtype=jnp.complex64)


# ---------------------------------------------------------------------
# --chips 4: the 2x2 grid beside a 1x1 grid of device 0
# ---------------------------------------------------------------------

def phase_four_chips(devices, n=32768, n_lu=N_LU, nrhs=8, nb=NB, seed=40):
    """``hpd_solve`` and ``lu_solve`` on ``el.Grid()`` over four devices
    (2x2), operands from the library's distributed fill, beside the same
    seed and N on a 1x1 grid of device 0 (``lu_solve`` at :data:`N_LU`,
    for the reason given there)."""
    if len(devices) != 4:
        raise AssertionError(f"--chips 4 needs four devices, "
                             f"found {len(devices)}")
    g4 = el.Grid(list(devices))
    g1 = el.Grid([devices[0]])
    if (g4.height, g4.width) != (2, 2):
        raise AssertionError(f"expected a 2x2 grid, got {g4}")
    for name, n, solve, gen_a in (
            ("hpd_solve", n, lambda A, B: el.hpd_solve(A, B, nb=nb), gen_hpd),
            ("lu_solve", n_lu, lambda A, B: el.lu_solve(A, B, nb=nb),
             lambda n_, g, s: gen_general(n_, n_, g, s))):
        sols = {}
        for tag, g in (("2x2", g4), ("1x1", g1)):
            X, line = _solve_line(
                "four_chips", name, solve,
                lambda g=g: gen_a(n, g, seed), (n, n), nrhs, g, seed, n,
                nb=nb)
            sols[tag] = np.asarray(el.to_global(X), np.float64)
            if tag == "2x2":
                quarter = n * n * 4 // 4
                sizes = [p["shard_bytes"] for p in line["placement"]]
                if len(sizes) != 4 or any(b != quarter for b in sizes):
                    raise AssertionError(
                        f"{name}: A is not a quarter per device: {sizes}")
                if not sum(line["collectives"].values()):
                    raise AssertionError(f"{name}: the 2x2 program holds "
                                         "no collective")
        diff = float(np.linalg.norm(sols["2x2"] - sols["1x1"])
                     / np.linalg.norm(sols["1x1"]))
        emit("four_chips", op=name, n=n, compare="2x2 vs 1x1",
             solution_diff=diff, tol=tol(n))
        if not diff <= tol(n):
            raise AssertionError(f"{name}: 2x2 and 1x1 solutions differ "
                                 f"by {diff:.3e} > {tol(n):.3e}")


# ---------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    try:
        if devs[0].platform != "tpu":
            raise RuntimeError(f"chip_smoke needs a TPU; JAX found "
                               f"{devs[0].platform!r}")
        if len(devs) != args.chips:
            raise RuntimeError(f"--chips {args.chips} but JAX found "
                               f"{len(devs)} device(s)")
        import jaxlib
        from importlib.metadata import version
        emit("start", jax=jax.__version__, jaxlib=jaxlib.__version__,
             libtpu=version("libtpu"), compile_cache=enable_compile_cache(),
             tuning_cache=el.tune.cache_dir(), **device)
        if args.chips == 4:
            phase_four_chips(devs)
        else:
            grid = el.Grid()
            phase_library(grid)
            phase_kernels(grid)
            phase_serving(devs)
            phase_complex(grid)
    except Exception as e:               # the boundary: report, then fail
        import traceback
        traceback.print_exc()
        print(json.dumps({"ok": False, "device": device,
                          "error": f"{type(e).__name__}: {e}"[:2000]}),
              flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
