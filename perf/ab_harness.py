"""A/B perf harness for the single-chip Cholesky/LU schedules.

Runs several schedule variants IN ONE PROCESS on the real chip, bracketing
each timing with a matmul roofline measurement so chip-weather is factored
out per-variant (the r4 lesson: never land a "perf" change without a
before/after pair).  Usage:

    python perf/ab_harness.py chol          # _potrf_inv variants at N=32768
    python perf/ab_harness.py lu [N]        # LU: classic vs look-ahead,
                                            #   nb + _INNERS sweep (dflt 16384)
    python perf/ab_harness.py cholesky [N]  # Cholesky: classic vs look-ahead
                                            #   x nb x crossover (dflt 16384)
    python perf/ab_harness.py lu-dist [N]   # distributed LU: classic-panel
                                            #   vs CALU tournament panel x
                                            #   look-ahead x tail crossover
                                            #   x comm_precision wire sweep
                                            #   on ALL visible devices
    python perf/ab_harness.py gemm [N]      # ISSUE 16: the full gemm alg
                                            #   family (A/B/C/dot/gspmd/
                                            #   slice/auto) x shape class
                                            #   (square / tall-skinny m>>n /
                                            #   outer-product k-small) on
                                            #   ALL visible devices, plus
                                            #   comm_precision twins of the
                                            #   slice rows
    python perf/ab_harness.py panel [M]     # ISSUE 17: the three panel
                                            #   primitives, xla op-ladder vs
                                            #   fused Pallas kernel, nb in
                                            #   {64..2048} x dtype (panel
                                            #   height M, dflt 16384/1024)
    python perf/ab_harness.py phases [lu|cholesky] [N NB]
                                            # per-step phase wall-clock as
                                            #   one phase_timings/v1 JSON line

``lu`` is the look-ahead A/B pair from ISSUE 1; ``cholesky`` is ISSUE 2's:
the first two variants are the classic right-looking schedule and the
pipelined look-ahead schedule at identical nb, same process, roofline
bracketed; the rest sweep nb and (on a multi-device grid, where the
distributed loop runs) the tail crossover-to-local threshold.  The
harness uses ALL visible devices -- on CPU export
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to exercise the
distributed schedule without hardware.

``phases`` drives ``elemental_tpu.obs.PhaseTimer`` through the real driver
(eagerly, sync at each phase boundary) and emits the ``phase_timings/v1``
JSON -- the hook future perf PRs use to attribute regressions.

``lu-dist`` and ``cholesky`` additionally sweep the ISSUE-8
``comm_precision`` wire-quantization knob on multi-device grids: each
quantized row is the exact twin of the headline look-ahead schedule at
equal nb/crossover/panel, so a row pair is a pure wire-precision A/B
(and the row prints the factor residual next to the throughput -- the
accuracy cost of the narrow wire is part of the measurement).  Override
the swept modes with ``--comm-precision bf16,int8`` (or ``none`` to
disable).
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

import importlib                                              # noqa: E402

import elemental_tpu as el                                    # noqa: E402
from elemental_tpu.core.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

chol_mod = importlib.import_module("elemental_tpu.lapack.cholesky")
lu_mod = importlib.import_module("elemental_tpu.lapack.lu")

HI = jax.lax.Precision.HIGHEST
DEF = jax.lax.Precision.DEFAULT


def _min3(fn, reps=3):
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return min(ts)


LAT = None
_ROOF_R = None


def roofline():
    global LAT, _ROOF_R
    if LAT is None:
        tiny = jax.jit(lambda x: x + 1.0)
        t = jnp.zeros(())
        float(tiny(t))
        LAT = _min3(lambda: float(tiny(t)))
    # CPU smoke runs: the fixed probe would dominate the sweep (minutes
    # per bracket at HIGHEST precision); the weather-tracking bracket only
    # needs a consistent in-run yardstick, not the TPU-saturating size
    n = 8192 if jax.devices()[0].platform != "cpu" else 512
    if _ROOF_R is None:
        _ROOF_R = jax.random.normal(jax.random.PRNGKey(9), (n, n), jnp.float32)
    mm = jax.jit(lambda x: jnp.matmul(x, x, precision=HI))
    float(mm(_ROOF_R)[0, 0])
    dt = max(_min3(lambda: float(mm(_ROOF_R)[0, 0])) - LAT, 1e-9)
    return 2 * n ** 3 / dt / 1e12


def timed(make_input, step, reps=3):
    out = step(make_input())
    jax.block_until_ready(out)
    times = []
    for _ in range(reps):
        A = make_input()
        float(jax.tree_util.tree_leaves(A)[0].ravel()[0])
        t0 = time.perf_counter()
        out = step(A)
        float(jax.tree_util.tree_leaves(out)[0].ravel()[0])
        times.append(time.perf_counter() - t0)
    del out
    return max(min(times) - LAT, 1e-9)


def report(name, tflops, roof, extra=""):
    print(f"{name:44s} {tflops:8.3f} TFLOP/s   roof {roof:6.2f}"
          f"   norm {100 * tflops / roof:5.1f}%{extra}", flush=True)


def run_chol():
    n, grid = 32768, el.Grid([jax.devices()[0]])

    @jax.jit
    def gen():
        G = jax.random.normal(jax.random.PRNGKey(0), (n, n), jnp.float32)
        return jnp.matmul(G, G.T) / n + n * jnp.eye(n, dtype=jnp.float32)

    def wrap(a):
        return el.DistMatrix(a, (n, n), el.MC, el.MR, 0, 0, grid)

    from jax import lax

    def native_potrf_inv(D, precision, bs=512):
        w = D.shape[0]
        d = jnp.tril(D)
        d = d + jnp.conj(jnp.tril(d, -1)).T
        L = jnp.linalg.cholesky(d)
        Li = lax.linalg.triangular_solve(L, jnp.eye(w, dtype=D.dtype),
                                         left_side=True, lower=True)
        return L, Li

    orig = chol_mod._potrf_inv
    variants = []
    for nb in (2048, 4096):
        variants.append((f"r4 _potrf_inv bs512 nb={nb}", orig, nb))
    variants.append(("native potrf+trsm-inv nb=2048", native_potrf_inv, 2048))
    variants.append(("_potrf_inv bs1024 nb=4096",
                     lambda D, p, bs=1024: orig(D, p, bs), 4096))
    variants.append(("_potrf_inv bs1024 nb=2048",
                     lambda D, p, bs=1024: orig(D, p, bs), 2048))

    for name, fn, nb in variants:
        chol_mod._potrf_inv = fn
        step = jax.jit(lambda a, _nb=nb: el.cholesky(a, nb=_nb,
                                                     precision=HI).local,
                       donate_argnums=0)
        r0 = roofline()
        dt = timed(lambda: wrap(gen()), step)
        r1 = roofline()
        report(name, (n ** 3 / 3) / dt / 1e12, 0.5 * (r0 + r1))
        del step
    chol_mod._potrf_inv = orig


def run_lu(n=None):
    on_tpu = jax.devices()[0].platform != "cpu"
    n = int(n) if n else (16384 if on_tpu else 512)
    grid = el.Grid([jax.devices()[0]])

    def wrap(a):
        return el.DistMatrix(a, (n, n), el.MC, el.MR, 0, 0, grid)

    gen = jax.jit(lambda: jax.random.normal(jax.random.PRNGKey(1), (n, n),
                                            jnp.float32))
    nb0 = 2048 if on_tpu else 128

    # (name, lookahead, inners, nb, update_precision, crossover, panel_impl)
    # xover=0 everywhere: this is the SINGLE-CHIP schedule harness (the
    # sequential path has no redistribution tail); the distributed LU
    # crossover A/B is `ab_harness.py lu-dist`, mirroring run_cholesky.
    # inners rides the lu(inners=) kwarg (NOT a lu_mod._INNERS
    # monkeypatch: since ISSUE 17 the resolved ladder flows through the
    # PanelPlan, so patching the module alias would silently go stale).
    cases = [
        (f"classic        inners=(512,64) nb={nb0}", False, (512, 64), nb0,
         None, 0, None),
        (f"look-ahead     inners=(512,64) nb={nb0}", True, (512, 64), nb0,
         None, 0, None),
        (f"look-ahead     inners=(512,64) nb={nb0 // 2}", True, (512, 64),
         nb0 // 2, None, 0, None),
        (f"look-ahead     inners=(512,64) nb={nb0 * 2}", True, (512, 64),
         nb0 * 2, None, 0, None),
        (f"look-ahead     inners=(768,96) nb={nb0}", True, (768, 96), nb0,
         None, 0, None),
        (f"look-ahead     inners=(1024,128) nb={nb0}", True, (1024, 128),
         nb0, None, 0, None),
        (f"look-ahead     inners=(512,128,32) nb={nb0}", True, (512, 128, 32),
         nb0, None, 0, None),
        (f"look-ahead+bf16upd inners=(512,64) nb={nb0}", True, (512, 64),
         nb0, DEF, 0, None),
        # panel_impl twin of the headline look-ahead row: equal
        # nb/inners/schedule, pure fused-kernel A/B (ISSUE 17).  Off-TPU
        # this times the interpret-mode kernel -- slower by construction,
        # the row documents it; the VMEM gate may silently route huge
        # panels back to xla (the resolved impl lands in bench.py
        # provenance, not here).
        (f"look-ahead     inners=(512,64) nb={nb0} panel=pallas", True,
         (512, 64), nb0, None, 0, "pallas"),
    ]

    for name, la, inners, nb, upd, xover, impl in cases:
        lufn = jax.jit(
            lambda a, _nb=nb, _la=la, _u=upd, _x=xover, _in=inners, _pi=impl:
            tuple(el.lu(a, nb=_nb, precision=HI, update_precision=_u,
                        lookahead=_la, crossover=_x, inners=_in,
                        panel_impl=_pi)),
            donate_argnums=0)

        def step(A):
            LU, perm = lufn(A)
            return LU.local, perm

        r0 = roofline()
        dt = timed(lambda: wrap(gen()), step)
        r1 = roofline()
        extra = ""
        if upd is not None:
            # residual at the relaxed trailing precision (documents the
            # bf16 knob's accuracy cost next to its speedup)
            LU, perm = lufn(wrap(gen()))
            mres = gen()
            v = jax.random.normal(jax.random.PRNGKey(3), (n, 1), jnp.float32)
            uv = jnp.matmul(jnp.triu(LU.local), v, precision=HI)
            luv = jnp.matmul(jnp.tril(LU.local, -1), uv, precision=HI) + uv
            pav = jnp.matmul(jnp.take(mres, perm, axis=0), v, precision=HI)
            resid = float(jnp.linalg.norm(pav - luv)
                          / (jnp.linalg.norm(mres) * jnp.linalg.norm(v)))
            extra = f"   resid {resid:.2e}"
            del LU, perm, mres
        report(name, (2 * n ** 3 / 3) / dt / 1e12, 0.5 * (r0 + r1), extra)
        del lufn


def run_lu_dist(n=None, cps=("bf16", "int8")):
    """ISSUE 3 + 6 A/B: distributed LU classic-panel vs CALU tournament
    panel, each under classic and look-ahead x tail-crossover schedules,
    same process and grid (all visible devices), roofline-bracketed --
    the LU twin of :func:`run_cholesky`.  On a single device the
    crossover rows are skipped (the sequential path has no redistribution
    tail to cross over from) and calu degenerates to classic (single
    grid row), so the tournament rows only appear on multi-row grids."""
    on_tpu = jax.devices()[0].platform != "cpu"
    n = int(n) if n else (16384 if on_tpu else 512)
    grid = el.Grid(jax.devices())
    p = grid.size
    nb0 = 2048 if on_tpu else 128

    gen = jax.jit(lambda: jax.random.normal(jax.random.PRNGKey(1), (n, n),
                                            jnp.float32))

    def wrap(a):
        return el.DistMatrix(a, (n, n), el.MC, el.MR, 0, 0, grid)

    # (name, lookahead, nb, crossover, panel, comm_precision)
    cases = [
        (f"classic        nb={nb0} xover=0", False, nb0, 0, "classic", None),
        (f"look-ahead     nb={nb0} xover=0", True, nb0, 0, "classic", None),
    ]
    if p > 1:
        for xo in (n // 8, n // 4, n // 2):
            cases.append((f"look-ahead     nb={nb0} xover={xo}",
                          True, nb0, xo, "classic", None))
        cases.append((f"classic        nb={nb0} xover={n // 4}",
                      False, nb0, n // 4, "classic", None))
        # wire-precision twins of the headline look-ahead row: equal
        # nb/crossover/panel, so each pair is a pure comm_precision A/B
        for cp in cps:
            cases.append((f"look-ahead     nb={nb0} xover=0 wire={cp}",
                          True, nb0, 0, "classic", cp))
    if grid.height > 1:
        # the calu twins of the headline schedules: equal nb/crossover so
        # every row pair is a pure panel-strategy A/B
        cases.append((f"calu           nb={nb0} xover=0",
                      True, nb0, 0, "calu", None))
        cases.append((f"calu classic-sched nb={nb0} xover=0",
                      False, nb0, 0, "calu", None))
        for xo in (n // 8, n // 4):
            cases.append((f"calu look-ahead nb={nb0} xover={xo}",
                          True, nb0, xo, "calu", None))
        for cp in cps:
            cases.append((f"calu           nb={nb0} xover=0 wire={cp}",
                          True, nb0, 0, "calu", cp))
    print(f"grid {grid.height}x{grid.width}, n={n}", flush=True)
    for name, la, nb, xo, pan, cp in cases:
        step = jax.jit(
            lambda a, _nb=nb, _la=la, _xo=xo, _p=pan, _c=cp: tuple(el.lu(
                a, nb=_nb, precision=HI, lookahead=_la, crossover=_xo,
                panel=_p, comm_precision=_c))[0].local,
            donate_argnums=0)
        r0 = roofline()
        dt = timed(lambda: wrap(gen()), step)
        r1 = roofline()
        report(name, (2 * n ** 3 / 3) / dt / 1e12, 0.5 * (r0 + r1))
        del step


def run_cholesky(n=None, cps=("bf16", "int8")):
    """ISSUE 2 A/B: classic vs look-ahead x nb x tail-crossover, same
    process and grid (all visible devices), roofline-bracketed.  On a
    single device the crossover rows are skipped (the sequential path has
    no redistribution tail to cross over from)."""
    on_tpu = jax.devices()[0].platform != "cpu"
    n = int(n) if n else (16384 if on_tpu else 512)
    grid = el.Grid(jax.devices())
    p = grid.size
    nb0 = 2048 if on_tpu else 128

    @jax.jit
    def gen():
        G = jax.random.normal(jax.random.PRNGKey(0), (n, n), jnp.float32)
        return jnp.matmul(G, G.T) / n + n * jnp.eye(n, dtype=jnp.float32)

    def wrap(a):
        return el.DistMatrix(a, (n, n), el.MC, el.MR, 0, 0, grid)

    # (name, lookahead, nb, crossover, comm_precision, panel_impl)
    cases = [
        (f"classic        nb={nb0} xover=0", False, nb0, 0, None, None),
        (f"look-ahead     nb={nb0} xover=0", True, nb0, 0, None, None),
        (f"look-ahead     nb={nb0 // 2} xover=0", True, nb0 // 2, 0, None,
         None),
        (f"look-ahead     nb={nb0 * 2} xover=0", True, nb0 * 2, 0, None,
         None),
        # panel_impl twin of the headline look-ahead row: equal
        # nb/crossover, pure fused-_potrf_inv A/B (ISSUE 17)
        (f"look-ahead     nb={nb0} xover=0 panel=pallas", True, nb0, 0,
         None, "pallas"),
    ]
    if p > 1:
        for xo in (n // 8, n // 4, n // 2):
            cases.append((f"look-ahead     nb={nb0} xover={xo}", True, nb0,
                          xo, None, None))
        cases.append((f"classic        nb={nb0} xover={n // 4}",
                      False, nb0, n // 4, None, None))
        # wire-precision twins of the headline look-ahead row (pure
        # comm_precision A/B at equal nb/crossover)
        for cp in cps:
            cases.append((f"look-ahead     nb={nb0} xover=0 wire={cp}",
                          True, nb0, 0, cp, None))
    print(f"grid {grid.height}x{grid.width}, n={n}", flush=True)
    for name, la, nb, xo, cp, impl in cases:
        step = jax.jit(
            lambda a, _nb=nb, _la=la, _xo=xo, _c=cp, _pi=impl: el.cholesky(
                a, nb=_nb, precision=HI, lookahead=_la, crossover=_xo,
                comm_precision=_c, panel_impl=_pi).local,
            donate_argnums=0)
        r0 = roofline()
        dt = timed(lambda: wrap(gen()), step)
        r1 = roofline()
        extra = ""
        if cp is not None:
            # accuracy cost of the narrow wire, printed inline.  The
            # timing rows feed gen()'s output as STORAGE (cheap, and
            # layout-irrelevant for wall-clock); the residual needs the
            # implied global matrix to really be SPD, so this one run
            # goes through the from_global/to_global bridges.
            from elemental_tpu import from_global, to_global
            a = gen()
            Ld = el.cholesky(from_global(a, el.MC, el.MR, grid=grid),
                             nb=nb, precision=HI, lookahead=la,
                             crossover=xo, comm_precision=cp)
            lg = to_global(Ld)
            v = jax.random.normal(jax.random.PRNGKey(2), (n, 1), jnp.float32)
            r = jnp.matmul(a, v, precision=HI) - jnp.matmul(
                lg, jnp.matmul(lg.T, v, precision=HI), precision=HI)
            resid = float(jnp.linalg.norm(r)
                          / (jnp.linalg.norm(a) * jnp.linalg.norm(v)))
            extra = f"   resid {resid:.2e}"
            del Ld, lg, a, v
        report(name, (n ** 3 / 3) / dt / 1e12, 0.5 * (r0 + r1), extra)
        del step


def run_gemm(n=None, cps=("bf16", "int8")):
    """ISSUE 16 A/B: the full gemm alg family x shape class, same
    process and grid (all visible devices), roofline-bracketed.

    Three shape classes cover the regimes the alg space splits on:
    ``square`` (the SUMMA home turf), ``tall-skinny`` (m >> n -- where
    the slicing schedule's three one-shot plans beat the panel rings;
    the bench.py ``gemm_tall_skinny_tflops_per_chip`` headline class)
    and ``outer-product`` (k small).  The ``auto`` row shows what the
    tuner dispatches per class, and the slice rows get comm_precision
    wire twins (equal shape/grid, pure wire-precision A/B).  Rows whose
    schedule cannot run the shape (e.g. dot's replicated-C blowup on
    huge squares) report ``skip`` instead of aborting the sweep."""
    on_tpu = jax.devices()[0].platform != "cpu"
    n = int(n) if n else (8192 if on_tpu else 256)
    grid = el.Grid(jax.devices())
    shapes = [("square", (n, n, n)),
              ("tall-skinny", (16 * n, n, max(n // 4, 1))),
              ("outer-product", (n, max(n // 16, 1), n))]
    algs = ["C", "A", "B", "dot", "gspmd", "slice", "auto"]
    print(f"grid {grid.height}x{grid.width}", flush=True)
    for cls, (m, k, nn) in shapes:
        print(f"-- {cls}: m={m} k={k} n={nn}", flush=True)
        gen = jax.jit(lambda _m=m, _k=k, _n=nn: (
            jax.random.normal(jax.random.PRNGKey(2), (_m, _k), jnp.float32),
            jax.random.normal(jax.random.PRNGKey(3), (_k, _n), jnp.float32)))

        def wrap(ab, _m=m, _k=k, _n=nn):
            a, b = ab
            return (el.from_global(a, el.MC, el.MR, grid=grid),
                    el.from_global(b, el.MC, el.MR, grid=grid))

        rows = [(a, None) for a in algs] + [("slice", cp) for cp in cps]
        for alg, cp in rows:
            name = f"{cls:13s} alg={alg}" + (f" wire={cp}" if cp else "")
            try:
                step = jax.jit(
                    lambda ab, _a=alg, _c=cp: el.gemm(
                        ab[0], ab[1], alg=_a, precision=HI,
                        comm_precision=_c).local,
                    donate_argnums=0)
                r0 = roofline()
                dt = timed(lambda: wrap(gen()), step)
                r1 = roofline()
                report(name, 2 * m * k * nn / dt / 1e12, 0.5 * (r0 + r1))
                del step
            except Exception as e:                     # noqa: BLE001
                print(f"{name:44s} skip ({type(e).__name__}: {e})",
                      flush=True)


def run_panel(n=None, dtypes=None):
    """ISSUE 17 A/B: the three panel primitives, xla op-ladder vs fused
    Pallas kernel, at matched inputs across the nb ladder x dtype --
    roofline-bracketed like every other sweep.  On TPU the pallas rows
    time the compiled Mosaic kernel; off-TPU they time the interpret-
    mode twin (the CPU CI artifact, slower by construction -- the rows
    exist so the gap is measured, not assumed).  Rows whose panel
    exceeds the fused kernel's VMEM budget report ``skip (vmem)``:
    the driver-level dispatch would route them back to xla."""
    from elemental_tpu import kernels
    qr_mod = importlib.import_module("elemental_tpu.lapack.qr")
    on_tpu = jax.devices()[0].platform != "cpu"
    m = int(n) if n else (16384 if on_tpu else 1024)
    if dtypes is None:
        dtypes = (jnp.float32,) if not jax.config.jax_enable_x64 \
            else (jnp.float32, jnp.float64)
    nbs = [nb for nb in (64, 128, 256, 512, 1024, 2048) if nb <= m]
    inner = kernels.default_inners()[-1]
    print(f"panel height m={m}, xla inner ladder {kernels.default_inners()}",
          flush=True)

    def sweep(prim, nb, dt, make, xla_fn, pal_fn, flops, copies):
        for impl, fn in (("xla", xla_fn), ("pallas", pal_fn)):
            name = f"{prim:5s} nb={nb:<5d} {jnp.dtype(dt).name:8s} {impl}"
            if impl == "pallas" and not kernels.panel_fits(
                    make().shape, dt, copies=copies):
                print(f"{name:44s} skip (vmem: dispatch would route to xla)",
                      flush=True)
                continue
            step = jax.jit(fn)
            r0 = roofline()
            dtime = timed(make, step)
            r1 = roofline()
            report(name, flops / dtime / 1e12, 0.5 * (r0 + r1))
            del step

    for dt in dtypes:
        for nb in nbs:
            key = jax.random.PRNGKey(nb)
            P0 = jax.random.normal(key, (m, nb), dt)
            G = jax.random.normal(key, (nb, nb), dt)
            D0 = jnp.matmul(G, G.T, precision=HI) / nb \
                + nb * jnp.eye(nb, dtype=dt)
            # lu: the chunked panel ladder vs the fused kernel at the
            # ladder's finest rung (what PanelPlan.pallas_inner selects)
            sweep("lu", nb, dt, lambda _p=P0: _p,
                  lambda p: lu_mod._panel_lu(p, nb, HI),
                  lambda p: kernels.lu_panel(p, nb, HI, inner=inner),
                  flops=m * nb * nb - nb ** 3 / 3, copies=3)
            # chol: blocked potrf+inverse pair on the diagonal block
            sweep("chol", nb, dt, lambda _d=D0: _d,
                  lambda d: chol_mod._potrf_inv(d, HI),
                  lambda d: kernels.potrf_inv(d, HI),
                  flops=nb ** 3, copies=4)
            # qr: larfg chain + larft build vs the fused single launch
            def xla_qr(p):
                packed, tau = qr_mod._panel_qr(p)
                V = qr_mod._panel_v(packed)
                return packed, tau, qr_mod._larft(V, tau)
            sweep("qr", nb, dt, lambda _p=P0: _p, xla_qr,
                  lambda p: kernels.qr_panel(p),
                  flops=2 * nb * nb * (m - nb / 3), copies=4)


def run_phases(*args):
    """Per-step phase wall-clock through the REAL driver (eager, PhaseTimer
    syncs at each boundary) -> one phase_timings/v1 JSON line.
    ``phases [lu|cholesky] [N NB]`` (driver defaults to lu)."""
    from elemental_tpu.obs import PhaseTimer
    args = list(args)
    driver = "lu"
    if args and not args[0].isdigit():
        driver = args.pop(0)
    n = int(args[0]) if args else None
    nb = int(args[1]) if len(args) > 1 else None
    on_tpu = jax.devices()[0].platform != "cpu"
    n = n or (16384 if on_tpu else 512)
    nb = nb or (2048 if on_tpu else 128)
    grid = el.Grid([jax.devices()[0]])
    t = PhaseTimer()
    if driver == "cholesky":
        G = jax.random.normal(jax.random.PRNGKey(0), (n, n), jnp.float32)
        a = jnp.matmul(G, G.T) / n + n * jnp.eye(n, dtype=jnp.float32)
        A = el.DistMatrix(a, (n, n), el.MC, el.MR, 0, 0, grid)
        jax.block_until_ready(a)
        L = el.cholesky(A, nb=nb, precision=HI, lookahead=True, timer=t)
        jax.block_until_ready(L.local)
        meta = dict(driver="cholesky", flops=n ** 3 / 3,
                    crossover=chol_mod._CROSSOVER)
    else:
        a = jax.random.normal(jax.random.PRNGKey(1), (n, n), jnp.float32)
        A = el.DistMatrix(a, (n, n), el.MC, el.MR, 0, 0, grid)
        jax.block_until_ready(a)
        LU, perm = el.lu(A, nb=nb, precision=HI, lookahead=True, timer=t)
        jax.block_until_ready((LU.local, perm))
        from elemental_tpu.kernels import default_inners
        meta = dict(driver="lu", flops=2 * n ** 3 / 3,
                    inners=list(default_inners()))
    r = roofline()
    print(t.json(n=n, nb=nb, lookahead=True, roofline_tflops=round(r, 2),
                 device=jax.devices()[0].device_kind, **meta), flush=True)


if __name__ == "__main__":
    argv = sys.argv[1:]
    cps = ("bf16", "int8")
    if "--comm-precision" in argv:
        i = argv.index("--comm-precision")
        raw = argv[i + 1] if i + 1 < len(argv) else "none"
        del argv[i: i + 2]
        cps = tuple(c for c in raw.split(",") if c and c != "none")
    mode = argv[0] if argv else "chol"
    tiny = jax.jit(lambda x: x + 1.0)
    t = jnp.zeros(())
    float(tiny(t))
    LAT = _min3(lambda: float(tiny(t)))
    if mode != "phases":
        print(f"device {jax.devices()[0].device_kind}, "
              f"rt latency {LAT*1e3:.2f} ms", flush=True)
    if mode == "chol":
        run_chol()
    elif mode == "lu":
        run_lu(*argv[1:2])
    elif mode == "lu-dist":
        run_lu_dist(*argv[1:2], cps=cps)
    elif mode == "cholesky":
        run_cholesky(*argv[1:2], cps=cps)
    elif mode == "gemm":
        run_gemm(*argv[1:2], cps=cps)
    elif mode == "panel":
        run_panel(*argv[1:2])
    else:
        run_phases(*argv[1:4])
