"""Driver benchmark: blocked Cholesky + HPL-style LU TFLOPS on the local chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"} for the
headline Cholesky config, plus "lu_*" keys for the LU entry and "gemm_*"
keys for the tall-skinny rectangular GEMM entry (ISSUE 16; the driver
metric names all three).  vs_baseline = measured TFLOP/s / north-star (60% of the
chip's fp32-class matmul peak; BASELINE.json "north_star").  fp32-class =
HIGHEST precision (6-pass bf16), so the peak table is bf16-peak / 6.

Memory budget (v5e: 16 GB HBM): at N = 32768 the operand is 4.3 GB, so the
factorization jit DONATES its input and every rep regenerates the matrix
on device from the same PRNG key (untimed).  Residual checks are matvec
based (||A v - L L^T v||), so they cost O(n^2) and no extra buffers.

The bench runs on a TPU or not at all: a run that finds no chip, or a
chip whose peak is not in the table below, fails.  Timing is the host
clock around work that ends in ``jax.block_until_ready``.

``--phases`` additionally drives one EAGER Cholesky through the
``elemental_tpu.obs.PhaseTimer`` hook and emits its per-step
diag/panel/update breakdown as a second ``phase_timings/v1`` JSON line
after the headline (at a reduced N: the eager run holds more live
buffers than the donate-input jit).

The headline line embeds a versioned ``"obs"`` key (``obs_bench/v1``):
the run's ``obs_metrics/v1`` document (op invocation counts, tuner cache
events, phase histograms) plus the ``--phases`` totals -- the trail
``tools/bench_diff.py`` gates and future perf PRs attribute against
(ISSUE 5).
"""
import json
import sys
import time

import jax
import jax.numpy as jnp


#: dense-matmul bf16 peaks per chip, TFLOP/s (vendor-published; there is no
#: runtime API for peak FLOPs, so this is keyed on ``device.device_kind``).
_BF16_PEAKS = {
    "v5 lite": 197.0,    # v5e
    "v5p": 459.0,
    "v5": 459.0,         # bare "TPU v5" reports as v5p
    "v4": 275.0,
    "v6 lite": 918.0,    # v6e (Trillium)
    "v6": 918.0,
}


def _fp32_peak(kind: str) -> float:
    """fp32-class peak of ``kind``; a device not in the table is an error."""
    for key, bf16 in sorted(_BF16_PEAKS.items(), key=lambda kv: -len(kv[0])):
        if key in kind.lower():
            return bf16 / 6.0
    raise SystemExit(f"bench: no peak recorded for device kind {kind!r}; "
                     f"add it to _BF16_PEAKS with its source")


def main():
    import elemental_tpu as el
    from elemental_tpu.core.compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, found {dev.platform!r}")
    enable_compile_cache()

    # Wire-byte accounting (ISSUE 8): a lightweight engine observer
    # totals the ring-model byte estimate of every public redistribute /
    # panel_spread entry at BOTH the logical dtype and the actual wire
    # dtype (the two differ under comm_precision).  Entries fire at
    # trace time, so jit-compiled reps count once per traced schedule --
    # the totals are "estimated bytes per factorization", the same
    # quantity the comm-plan goldens pin.
    from elemental_tpu.redist.engine import add_redist_observer
    from elemental_tpu.obs.tracer import ring_bytes
    _wire_totals = {"redist_bytes": 0, "redist_wire_bytes": 0}

    def _on_redist(rec):
        grid_shape = getattr(rec, "grid_shape", ())
        _wire_totals["redist_bytes"] += ring_bytes(
            rec.gshape, rec.dtype, grid_shape)
        wire = getattr(rec, "wire_dtype", "") or rec.dtype
        _wire_totals["redist_wire_bytes"] += ring_bytes(
            rec.gshape, wire, grid_shape)

    _unobserve = add_redist_observer(_on_redist)

    # N=32768: a 4.3 GB operand on a 16 GB chip, feasible because the
    # bench path donates its input (the operand is regenerated per rep).
    n_chol = n_lu = 32768
    nb = 2048
    grid = el.Grid([dev])
    HI = jax.lax.Precision.HIGHEST

    # The baseline is the fp32-class matmul roofline MEASURED IN THIS RUN
    # (capped by the nameplate table).
    table_peak = _fp32_peak(dev.device_kind)
    nroof = 8192
    R = jax.random.normal(jax.random.PRNGKey(9), (nroof, nroof), jnp.float32)
    mm = jax.jit(lambda x: jnp.matmul(x, x, precision=HI))
    jax.block_until_ready(mm(R))
    ts = []
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(mm(R))
        ts.append(time.perf_counter() - t0)
    roofline = min(2 * nroof ** 3 / min(ts) / 1e12, table_peak)
    del R
    north_star = 0.6 * roofline

    def wrap(a, n):
        return el.DistMatrix(a, (n, n), el.MC, el.MR, 0, 0, grid)

    def timed(make_input, step, reps=3):
        """min-of-reps wall time; the input is regenerated (untimed) per rep
        because ``step`` donates it."""
        out = step(make_input())       # compile + warm
        jax.block_until_ready(out)
        times = []
        for _ in range(reps):
            A = jax.block_until_ready(make_input())
            t0 = time.perf_counter()
            out = jax.block_until_ready(step(A))
            times.append(time.perf_counter() - t0)
        return out, min(times)

    # ---- Cholesky (SPD solve headline config) -------------------------
    @jax.jit
    def gen_spd():
        G = jax.random.normal(jax.random.PRNGKey(0), (n_chol, n_chol),
                              jnp.float32)
        return jnp.matmul(G, G.T) / n_chol \
            + n_chol * jnp.eye(n_chol, dtype=jnp.float32)

    chol = jax.jit(lambda a: el.cholesky(a, nb=nb, precision=HI).local,
                   donate_argnums=0)
    l_arr, dt = timed(lambda: wrap(gen_spd(), n_chol), chol)
    chol_tflops = (n_chol ** 3 / 3) / dt / 1e12

    # untimed matvec residual: ||A v - L (L^T v)|| / (||A||_F ||v||)
    @jax.jit
    def chol_resid(l):
        a = gen_spd()
        v = jax.random.normal(jax.random.PRNGKey(2), (n_chol, 1), jnp.float32)
        r = jnp.matmul(a, v, precision=HI) \
            - jnp.matmul(l, jnp.matmul(l.T, v, precision=HI), precision=HI)
        return jnp.linalg.norm(r) / (jnp.linalg.norm(a) * jnp.linalg.norm(v))

    resid = float(chol_resid(l_arr))
    del l_arr
    if resid > 1e-3 or resid != resid:
        print(json.dumps({"metric": f"cholesky_n{n_chol}_tflops_per_chip",
                          "value": 0.0, "unit": "TFLOP/s", "vs_baseline": 0.0,
                          "error": f"cholesky residual {resid:.3e}"}))
        return 1

    # ---- LU with partial pivoting (HPL-style) -------------------------
    def gen_lu():
        return jax.random.normal(jax.random.PRNGKey(1), (n_lu, n_lu),
                                 jnp.float32)

    lufn = jax.jit(lambda a: tuple(el.lu(a, nb=nb, precision=HI)),
                   donate_argnums=0)

    def lu_step(A):
        LU, perm = lufn(A)
        return LU.local, perm

    (lu_arr, perm), dt_lu = timed(lambda: wrap(jax.jit(gen_lu)(), n_lu), lu_step)
    lu_tflops = (2 * n_lu ** 3 / 3) / dt_lu / 1e12

    @jax.jit
    def lu_resid_fn(lu_loc, perm):
        m = gen_lu()
        v = jax.random.normal(jax.random.PRNGKey(3), (n_lu, 1), jnp.float32)
        pav = jnp.matmul(jnp.take(m, perm, axis=0), v, precision=HI)
        # unit-lower L: L (U v) = tril(lu,-1) (U v) + (U v)
        uv = jnp.matmul(jnp.triu(lu_loc), v, precision=HI)
        luv = jnp.matmul(jnp.tril(lu_loc, -1), uv, precision=HI) + uv
        return jnp.linalg.norm(pav - luv) / (jnp.linalg.norm(m)
                                             * jnp.linalg.norm(v))

    lu_resid = float(lu_resid_fn(lu_arr, perm))
    if lu_resid > 1e-3 or lu_resid != lu_resid:
        print(json.dumps({"metric": f"lu_n{n_lu}_tflops_per_chip",
                          "value": 0.0, "unit": "TFLOP/s", "vs_baseline": 0.0,
                          "error": f"lu residual {lu_resid:.3e}",
                          "cholesky_value": round(chol_tflops, 3)}))
        return 1

    # ---- rectangular GEMM (ISSUE 16: the tall-skinny headline) --------
    # The serving tier's real matmul class: m >> n.  alg='auto' so the
    # timed run IS the tuner's dispatch (provenance recorded below --
    # 'dot' on this single-chip grid via the pinned early-out, 'slice'
    # on the multi-chip tall-skinny grids).
    m_g, k_g, n_g = 65536, 512, 512

    @jax.jit
    def gen_gemm():
        return (jax.random.normal(jax.random.PRNGKey(4), (m_g, k_g),
                                  jnp.float32),
                jax.random.normal(jax.random.PRNGKey(5), (k_g, n_g),
                                  jnp.float32))

    def wrap_gemm(ab):
        a, b = ab
        return (el.DistMatrix(a, (m_g, k_g), el.MC, el.MR, 0, 0, grid),
                el.DistMatrix(b, (k_g, n_g), el.MC, el.MR, 0, 0, grid))

    gemm_fn = jax.jit(
        lambda ab: el.gemm(ab[0], ab[1], alg="auto", precision=HI).local,
        donate_argnums=0)
    c_arr, dt_g = timed(lambda: wrap_gemm(gen_gemm()), gemm_fn)
    gemm_tflops = 2 * m_g * k_g * n_g / dt_g / 1e12

    @jax.jit
    def gemm_resid_fn(c_loc):
        a, b = gen_gemm()
        v = jax.random.normal(jax.random.PRNGKey(6), (n_g, 1), jnp.float32)
        r = jnp.matmul(c_loc, v, precision=HI) \
            - jnp.matmul(a, jnp.matmul(b, v, precision=HI), precision=HI)
        return jnp.linalg.norm(r) / (jnp.linalg.norm(a) * jnp.linalg.norm(b)
                                     * jnp.linalg.norm(v))

    gemm_resid = float(gemm_resid_fn(c_arr))
    del c_arr
    if gemm_resid > 1e-3 or gemm_resid != gemm_resid:
        print(json.dumps({"metric": f"cholesky_n{n_chol}_tflops_per_chip",
                          "value": round(chol_tflops, 3), "unit": "TFLOP/s",
                          "error": f"gemm residual {gemm_resid:.3e}",
                          "lu_value": round(lu_tflops, 3)}))
        return 1

    # Tuner self-description (ISSUE 4 + 6): record the config the autotuner
    # resolves for each headline op -- and whether it came from a measured
    # cache entry or the analytic cost model -- so this BENCH line says
    # not just how fast, but under WHICH knobs a tuned run would execute.
    # Since ISSUE 6 the LU resolution includes the panel strategy
    # ('classic' | 'calu'): on this single-chip grid 'auto' resolves to
    # 'classic' (calu degenerates on single-row grids), and a multi-row
    # bench would record 'calu' here -- the provenance the trajectory
    # gate reads next to the renamed lu_n32768 metric.  (The timed runs
    # above use the pinned nb/panel for baseline comparability.)
    # panel_impl + inners join ran_with (ISSUE 17): the timed runs above
    # execute the status-quo XLA panel ladder at the pinned chunk widths
    # (read from kernels.default_inners(), the single source -- NOT the
    # lu module alias, which a tuner/harness override would leave stale),
    # and the per-op resolutions below record which implementation
    # 'auto' would dispatch on this backend.
    from elemental_tpu.kernels import default_inners
    tuner: dict = {"ran_with": {"nb": nb, "lookahead": True,
                                "crossover": None, "panel": "classic",
                                "comm_precision": None,
                                "redist_path": None,
                                "panel_impl": None,
                                "inners": list(default_inners())}}
    from elemental_tpu import tune as el_tune
    for op, gshape in (("cholesky", (n_chol, n_chol)),
                       ("lu", (n_lu, n_lu)),
                       ("gemm", (m_g, k_g, n_g))):
        # comm_precision joins the resolved provenance (ISSUE 8): on
        # this single-chip grid 'auto' resolves to None (the knob is
        # dead without collectives); a multi-device bench records the
        # tuner's wire-precision pick here next to nb/panel
        # redist_path joins the provenance (ISSUE 12/13): 'auto'
        # resolves chain vs one-shot per grid -- None on single-chip
        # (every plan is 'local'), and a multi-chip bench records the
        # arbiter's pick (measured constants when recorded, the ring
        # model otherwise) next to nb/panel
        if op == "gemm":
            # the gemm headline's provenance (ISSUE 16): which alg
            # family the tuner dispatched the tall-skinny class to --
            # 'dot' on this single-chip grid (pinned early-out),
            # 'slice' on multi-chip tall-skinny grids
            requested = {"alg": "auto", "nb": "auto",
                         "comm_precision": "auto",
                         "redist_path": "auto"}
        else:
            requested = {"nb": "auto", "lookahead": "auto",
                         "crossover": "auto", "comm_precision": "auto",
                         "redist_path": "auto", "panel_impl": "auto"}
            if op == "lu":
                requested["panel"] = "auto"
        res = el_tune.resolve(
            op, gshape=gshape, dtype=jnp.float32, grid=grid,
            requested=requested)
        tuner[op] = {"config": dict(res.config), "source": res.source}
    tuner["cache_dir"] = el_tune.cache_dir()

    ph_line = None
    ph_summary = None
    if "--phases" in sys.argv[1:]:
        # cholesky phase attribution alongside the headline: one eager run
        # through the PhaseTimer hook (smaller N -- the eager driver
        # cannot donate its input)
        from elemental_tpu.obs import PhaseTimer
        del lu_arr, perm
        n_ph = min(n_chol, 16384)

        @jax.jit
        def gen_ph():
            G = jax.random.normal(jax.random.PRNGKey(0), (n_ph, n_ph),
                                  jnp.float32)
            return jnp.matmul(G, G.T) / n_ph \
                + n_ph * jnp.eye(n_ph, dtype=jnp.float32)

        Ap = wrap(gen_ph(), n_ph)
        jax.block_until_ready(Ap.local)
        t = PhaseTimer()
        Lp = el.cholesky(Ap, nb=nb, precision=HI, timer=t)
        jax.block_until_ready(Lp.local)
        ph_doc = t.report(driver="cholesky", n=n_ph, nb=nb, lookahead=True,
                          flops=n_ph ** 3 / 3,
                          device=dev.device_kind)
        ph_line = json.dumps(ph_doc)
        ph_summary = {"schema": ph_doc["schema"], "driver": "cholesky",
                      "n": n_ph, "nb": nb, "totals": ph_doc["totals"],
                      "total_seconds": ph_doc["total_seconds"]}
        del Lp, Ap

    # Observability doc (ISSUE 5): the run's metrics registry (op
    # invocation counts, tuner cache events, phase histograms from the
    # --phases run) plus the phase breakdown, under one versioned key --
    # the machine-readable trail tools/bench_diff.py and future perf PRs
    # read.
    from elemental_tpu.obs import metrics as obs_metrics
    from perf.redist_bench import p2p_gbps
    obs_doc: dict = {"schema": "obs_bench/v1"}
    obs_doc["metrics"] = obs_metrics.current().to_doc(device=dev.device_kind)
    obs_doc["phases"] = ph_summary
    # estimated redistribution bytes, logical vs on-the-wire (equal
    # unless a comm_precision mode ran); tools/bench_diff.py accepts
    # the new key without tripping its rename guard
    obs_doc["redist_bytes"] = int(_wire_totals["redist_bytes"])
    obs_doc["redist_wire_bytes"] = int(_wire_totals["redist_wire_bytes"])
    _unobserve()
    # chain-vs-direct redistribution GB/s for one representative move on
    # ALL visible chips (ISSUE 12) -- informational only, never gated by
    # bench_diff; on a 1-chip host both rates are 0.0 (no wire bytes in
    # the ring model)
    obs_doc["redist_p2p_gbps"] = p2p_gbps(el.Grid(jax.devices()))

    print(json.dumps({
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "metric": f"cholesky_n{n_chol}_tflops_per_chip",
        "value": round(chol_tflops, 3),
        "unit": "TFLOP/s",
        "vs_baseline": round(chol_tflops / north_star, 4),
        "lu_metric": f"lu_n{n_lu}_tflops_per_chip",
        "lu_value": round(lu_tflops, 3),
        "lu_vs_baseline": round(lu_tflops / north_star, 4),
        "gemm_metric": "gemm_tall_skinny_tflops_per_chip",
        "gemm_value": round(gemm_tflops, 3),
        "gemm_vs_baseline": round(gemm_tflops / north_star, 4),
        "gemm_dims": [m_g, k_g, n_g],
        "vs_nameplate": round(chol_tflops / (0.6 * table_peak), 4),
        "lu_vs_nameplate": round(lu_tflops / (0.6 * table_peak), 4),
        "roofline_tflops": round(roofline, 2),
        "nameplate_tflops": round(table_peak, 2),
        "resid": f"{resid:.2e}",
        "lu_resid": f"{lu_resid:.2e}",
        "gemm_resid": f"{gemm_resid:.2e}",
        "tuner": tuner,
        "obs": obs_doc,
    }))

    if ph_line is not None:
        print(ph_line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
