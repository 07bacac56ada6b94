"""Runtime tracing CLI (ISSUE 5): one eager driver run -> Perfetto trace
+ ``obs_metrics/v1`` document.

The command-line face of ``elemental_tpu/obs``:

    python -m perf.trace run cholesky 4096 --out trace.json
                                            # trace one driver: nested
                                            #   driver/step/phase spans +
                                            #   collective instants ->
                                            #   Chrome-trace JSON (load it
                                            #   at https://ui.perfetto.dev)
                                            #   + one obs_metrics/v1 line
    python -m perf.trace run lu --n 256 --nb 64 --grid 2x2
    python -m perf.trace summary trace.json # per-lane totals of a trace
    python -m perf.trace export phases.json --out trace.json
                                            # convert a phase_timings/v1
                                            #   doc (PhaseTimer.json()) to
                                            #   the same trace format
    python -m perf.trace serve --out trace.json
                                            # drive a small 2-grid fleet
                                            #   workload (ISSUE 20): the
                                            #   trace carries one track
                                            #   per grid worker plus flow
                                            #   arrows linking each
                                            #   request submit -> worker
                                            #   -> done; also emits the
                                            #   serve_slo/v1 snapshot and
                                            #   a chaos-triggered
                                            #   flight_record/v1 dump

Flags for ``serve``: ``--requests N`` (default 12), ``--grids G``
(default 2), ``--out trace.json``, ``--slo-out slo.json``,
``--flight-out flight.json``, ``--smoke`` (self-check mode: validate
every timeline with ``check_timeline``, require flow events + >= 2
grid-worker tracks in the export, a non-trivial per-tenant SLO
snapshot, and a BIT-IDENTICAL flight-record replay of the grid-loss
chaos cell under the virtual clock; exit 1 on any failure).

Drivers: ``cholesky``, ``lu``, ``qr``, ``gemm``, ``trsm``, ``herk`` (the
six tuned drivers -- all emit spans through ``obs.phase_hook``).  The run
is EAGER (the tracer syncs at every phase boundary; same caveat as
``PhaseTimer``) on the real backend; under ``JAX_PLATFORMS=cpu`` an
8-virtual-device host mesh makes multi-device grids (``--grid 2x2``)
available anywhere, which is what the ``tools/check.sh`` smoke uses.

Flags for ``run``: ``--n N`` (or positional; default 2048 on TPU / 64 on
CPU), ``--nb NB``, ``--grid RxC`` (default 2x2 when >= 4 devices, else
1x1), ``--dtype NAME``, ``--alg {A,B,C,dot,gspmd,auto}`` (gemm),
``--classic`` (lookahead off), ``--crossover X``, ``--out trace.json``,
``--metrics-out metrics.json``.  The metrics document always prints to
stdout as the final line; summary rows are ``#``-prefixed above it.
"""
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DRIVERS = ("cholesky", "lu", "qr", "gemm", "trsm", "herk")


def _bootstrap() -> None:
    """Virtual 8-device mesh on CPU hosts, BEFORE jax initializes (the
    backend itself is whatever the environment provides -- runtime traces
    should see the real chip when there is one)."""
    if _REPO not in sys.path:
        sys.path.insert(0, _REPO)
    import jax
    try:
        jax.config.update("jax_num_cpu_devices", 8)
    except RuntimeError:
        pass      # backend already initialized


def _grid(spec: str | None):
    import jax
    from elemental_tpu.core.grid import Grid
    devs = jax.devices()
    if spec is None:
        if len(devs) >= 4:
            return Grid(devs[:4], height=2)
        return Grid(devs[:1])
    r, c = (int(x) for x in spec.split("x"))
    if r * c > len(devs):
        raise SystemExit(f"grid {r}x{c} needs {r * c} devices, have {len(devs)}")
    return Grid(devs[: r * c], height=r)


def _run_driver(driver, grid, n, nb, lookahead, crossover, alg, dtype):
    """Build inputs EAGERLY (outside the trace), run the driver once, and
    return the output leaves (synced by the caller's span)."""
    import numpy as np
    import elemental_tpu as el
    rng = np.random.default_rng(0)
    F = rng.normal(size=(n, n)).astype(dtype)
    kw = {}
    if driver in ("cholesky", "lu"):
        kw = {"lookahead": lookahead, "crossover": crossover}
    if driver in ("cholesky", "trsm", "herk"):
        S = (F @ F.T / n + n * np.eye(n)).astype(dtype)
        A = el.from_global(S, el.MC, el.MR, grid=grid)
    else:
        A = el.from_global(F + n * np.eye(n, dtype=dtype), el.MC, el.MR,
                           grid=grid)
    if driver in ("gemm", "trsm"):
        B = el.from_global(rng.normal(size=(n, n)).astype(dtype),
                           el.MC, el.MR, grid=grid)
    import jax
    jax.block_until_ready(A.local)

    if driver == "cholesky":
        return el.cholesky(A, nb=nb, **kw).local
    if driver == "lu":
        LU, perm = el.lu(A, nb=nb, **kw)
        return (LU.local, perm)
    if driver == "qr":
        Ap, tau = el.qr(A, nb=nb)
        return (Ap.local, tau)
    if driver == "gemm":
        return el.gemm(A, B, alg=alg, nb=nb).local
    if driver == "trsm":
        return el.trsm("L", "L", "N", A, B, nb=nb).local
    if driver == "herk":
        return el.herk("L", A, nb=nb).local
    raise SystemExit(f"unknown driver {driver!r}; known: {DRIVERS}")


def cmd_run(driver, n, nb, grid_spec, dtype_name, alg, lookahead, crossover,
            out, metrics_out) -> int:
    import jax
    from elemental_tpu import obs
    grid = _grid(grid_spec)
    if n is None:
        n = 2048 if jax.devices()[0].platform != "cpu" else 64
    meta = {"driver": driver, "n": n, "nb": nb,
            "grid": f"{grid.height}x{grid.width}", "dtype": dtype_name,
            "device": getattr(jax.devices()[0], "device_kind",
                              jax.devices()[0].platform)}
    with obs.metrics_scope() as reg:
        tracer = obs.Tracer()
        with tracer:
            with tracer.span("run", **meta) as sp:
                leaves = _run_driver(driver, grid, n, nb, lookahead,
                                     crossover, alg, dtype_name)
                jax.block_until_ready(leaves)
        trace_doc = obs.chrome_trace_doc(tracer, **meta)
        mdoc = reg.to_doc(**meta)
    if out:
        obs.write_json(out, trace_doc)
        print(f"# trace: {out}  ({len(trace_doc['traceEvents'])} events; "
              "load at https://ui.perfetto.dev)")
    for drv, totals in tracer.phase_totals().items():
        row = "  ".join(f"{p}={t * 1e3:.2f}ms" for p, t in totals.items())
        print(f"# phases[{drv}]: {row}")
    rc = tracer.redist_counts()
    print(f"# collectives: {sum(rc.values())} redistribute/panel_spread "
          f"entries, ~{tracer.redist_bytes_total()} ring-model bytes")
    if metrics_out:
        obs.write_json(metrics_out, mdoc)
        print(f"# metrics: {metrics_out}")
    print(json.dumps(mdoc))
    return 0


def cmd_serve(requests, grids, out, slo_out, flight_out, smoke) -> int:
    """Drive a small pipelined fleet workload under the tracer and emit
    the three ISSUE-20 artifacts: Chrome trace (flow-linked lifecycle),
    ``serve_slo/v1`` snapshot, ``flight_record/v1`` dump."""
    from elemental_tpu import obs
    from elemental_tpu.obs.lifecycle import check_timeline
    from elemental_tpu.serve.chaos import build_workload
    from elemental_tpu.serve.fleet import SolverFleet

    requests = 12 if requests is None else int(requests)
    grids = 2 if grids is None else int(grids)
    tenants = ("acme", "blue")
    fleet = SolverFleet(grids=grids, depth=2, max_batch=4, shed=False,
                        retries=0)
    tracer = obs.Tracer()
    with tracer:
        with tracer.span("serve:fleet", grids=grids, requests=requests):
            work = build_workload("hpd", 16, 2, requests, seed=7)
            futs = [fleet.submit("hpd", A, B,
                                 tenant=tenants[i % len(tenants)])
                    for i, (A, B) in enumerate(work)]
            for f in futs:
                f.result(timeout=300.0)
            fleet.shutdown(drain=True)
    docs = [f.result(timeout=0)[1] for f in futs]
    problems = []
    for f, doc in zip(futs, docs):
        errs = check_timeline(doc.get("timeline"), path=doc.get("path"),
                              fleet=True)
        problems.extend(f"request f{f.fleet_id}: {e}" for e in errs)
    n_ok = sum(1 for d in docs if d.get("status") == "ok")
    print(f"# fleet: {grids} grids, {len(docs)} requests, {n_ok} ok, "
          f"{len(problems)} timeline problems")

    trace_doc = obs.chrome_trace_doc(tracer, mode="serve", grids=grids)
    evs = trace_doc["traceEvents"]
    flows = [ev for ev in evs if ev.get("ph") in ("s", "t", "f")]
    worker_tracks = {ev["args"]["name"] for ev in evs
                     if ev.get("ph") == "M"
                     and ev.get("name") == "thread_name"
                     and str(ev["args"]["name"])
                     .startswith("elemental-serve-worker")}
    print(f"# trace: {len(evs)} events, {len(flows)} flow events, "
          f"{len(worker_tracks)} grid-worker tracks")
    if out:
        obs.write_json(out, trace_doc)
        print(f"# trace file: {out} (load at https://ui.perfetto.dev)")

    sdoc = fleet.slo.snapshot(source="perf.trace serve")
    per_tenant = fleet.slo.per_tenant_p99_ms()
    for t in sorted(per_tenant):
        print(f"# slo[{t}]: p99={per_tenant[t]:.2f}ms")
    if slo_out:
        obs.write_json(slo_out, sdoc)
        print(f"# slo file: {slo_out}")

    # injected chaos trigger: dump the run's lifecycle record
    fdoc = fleet.flight.trigger("chaos_fault", source="perf.trace serve")
    edge_events = sum(1 for ev in fdoc["events"]
                      if str(ev.get("kind", "")).startswith("edge:"))
    print(f"# flight: {len(fdoc['events'])} events in dump "
          f"({edge_events} lifecycle edges, {fdoc['dropped']} dropped)")
    if flight_out:
        obs.write_json(flight_out, fdoc)
        print(f"# flight file: {flight_out}")

    if smoke:
        from elemental_tpu.serve.chaos import fleet_replay_identical
        if problems:
            for p in problems[:10]:
                print(f"SMOKE FAIL timeline: {p}", file=sys.stderr)
            return 1
        if n_ok != len(docs):
            print(f"SMOKE FAIL: only {n_ok}/{len(docs)} requests ok",
                  file=sys.stderr)
            return 1
        if not any(ev["ph"] == "s" for ev in flows) \
                or not any(ev["ph"] == "f" for ev in flows):
            print("SMOKE FAIL: export has no complete s->f flow chains",
                  file=sys.stderr)
            return 1
        if len(worker_tracks) < min(grids, 2):
            print(f"SMOKE FAIL: {len(worker_tracks)} grid-worker tracks "
                  f"in export, want >= {min(grids, 2)}", file=sys.stderr)
            return 1
        missing = [t for t in tenants if t not in per_tenant]
        if missing or not sdoc.get("series"):
            print(f"SMOKE FAIL: SLO snapshot incomplete "
                  f"(missing tenants {missing})", file=sys.stderr)
            return 1
        if edge_events == 0:
            print("SMOKE FAIL: flight dump has no lifecycle edges",
                  file=sys.stderr)
            return 1
        if not fleet_replay_identical(requests=4):
            print("SMOKE FAIL: grid-loss flight record not bit-identical "
                  "on replay", file=sys.stderr)
            return 1
        print("# smoke: timelines complete, flows linked, SLO per-tenant "
              "recorded, flight replay bit-identical")
    print(json.dumps(sdoc))
    return 0


def cmd_summary(path) -> int:
    with open(path) as f:
        doc = json.load(f)
    if "traceEvents" not in doc:
        raise SystemExit(f"{path}: not a Chrome trace document")
    names = {}
    for ev in doc["traceEvents"]:
        if ev.get("ph") == "M" and ev.get("name") == "thread_name":
            names[ev["tid"]] = ev["args"]["name"]
    lanes: dict = {}
    ninstant = nbytes = 0
    for ev in doc["traceEvents"]:
        if ev.get("ph") == "X":
            lane = names.get(ev.get("tid"), str(ev.get("tid")))
            cur = lanes.setdefault(lane, [0, 0.0])
            cur[0] += 1
            cur[1] += ev.get("dur", 0.0)
        elif ev.get("ph") == "i":
            ninstant += 1
            nbytes += ev.get("args", {}).get("bytes", 0)
    other = doc.get("otherData", {})
    print(f"# {path}: schema={doc.get('schema')} "
          + " ".join(f"{k}={v}" for k, v in sorted(other.items())))
    print(f"{'lane':24s} {'spans':>6s} {'total_ms':>10s}")
    for lane, (cnt, dur) in sorted(lanes.items(), key=lambda kv: -kv[1][1]):
        print(f"{lane:24s} {cnt:6d} {dur / 1e3:10.3f}")
    if ninstant:
        print(f"{'collectives':24s} {ninstant:6d} {'~' + str(nbytes):>10s}B")
    return 0


def cmd_export(path, out) -> int:
    from elemental_tpu import obs
    with open(path) as f:
        doc = json.load(f)
    trace = obs.phase_timings_to_chrome(doc)
    if out:
        obs.write_json(out, trace)
        print(f"# trace: {out}  ({len(trace['traceEvents'])} events)")
    else:
        print(json.dumps(trace))
    return 0


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help"):
        print(__doc__)
        return 0
    cmd = argv.pop(0)
    if cmd not in ("run", "summary", "export", "serve"):
        print(__doc__)
        raise SystemExit(f"unknown command {cmd!r}")
    pos = []
    n = nb = crossover = None
    grid_spec = out = metrics_out = None
    requests = serve_grids = slo_out = flight_out = None
    smoke = False
    dtype_name, alg, lookahead = "float32", "auto", True
    it = iter(argv)
    for arg in it:
        if arg == "--n":
            n = int(next(it))
        elif arg == "--requests":
            requests = int(next(it))
        elif arg == "--grids":
            serve_grids = int(next(it))
        elif arg == "--slo-out":
            slo_out = next(it)
        elif arg == "--flight-out":
            flight_out = next(it)
        elif arg == "--smoke":
            smoke = True
        elif arg == "--nb":
            nb = int(next(it))
        elif arg == "--grid":
            grid_spec = next(it)
        elif arg == "--dtype":
            dtype_name = next(it)
        elif arg == "--alg":
            alg = next(it)
        elif arg == "--classic":
            lookahead = False
        elif arg == "--crossover":
            crossover = int(next(it))
        elif arg == "--out":
            out = next(it)
        elif arg == "--metrics-out":
            metrics_out = next(it)
        elif arg.startswith("--"):
            raise SystemExit(f"unknown flag {arg!r}")
        else:
            pos.append(arg)
    if cmd == "run":
        if not pos:
            raise SystemExit(f"run needs a driver ({'/'.join(DRIVERS)})")
        driver = pos.pop(0)
        if driver not in DRIVERS:
            # before _bootstrap: no jax import, no device init, no
            # input-building -- just the registry and a clean exit 1
            print(f"unknown driver {driver!r}; registered drivers:",
                  file=sys.stderr)
            for d in DRIVERS:
                print(f"  {d}", file=sys.stderr)
            return 1
        if pos and n is None:
            n = int(pos.pop(0))
        _bootstrap()
        return cmd_run(driver, n, nb, grid_spec, dtype_name, alg, lookahead,
                       crossover, out, metrics_out)
    if cmd == "serve":
        _bootstrap()
        return cmd_serve(requests, serve_grids, out, slo_out, flight_out,
                         smoke)
    if not pos:
        raise SystemExit(f"{cmd} needs a JSON file path")
    if cmd == "summary":
        return cmd_summary(pos[0])
    _bootstrap()          # export imports elemental_tpu.obs (jax)
    return cmd_export(pos[0], out)


if __name__ == "__main__":
    try:
        import signal
        signal.signal(signal.SIGPIPE, signal.SIG_DFL)
    except (ImportError, AttributeError, ValueError):
        pass
    raise SystemExit(main())
