"""Autotuning CLI: search / show / clear / explain (ISSUE 4).

The command-line face of ``elemental_tpu/tune``:

    python -m perf.tune explain cholesky                 # cost-model
                                                         #   breakdown per
                                                         #   candidate
    python -m perf.tune explain gemm --n 8192 --grid 2x2
    python -m perf.tune search cholesky --n 4096         # MEASURE the top
                                                         #   cost-ranked
                                                         #   configs, record
                                                         #   the winner
    python -m perf.tune show [op]                        # cache contents
    python -m perf.tune clear [op]                       # drop entries

``explain`` and the cache commands are trace-only / filesystem-only: they
force an 8-virtual-device CPU backend (like ``perf.comm_audit``) and run
identically on any host; ``explain`` doubles as the cost-model self-check
wired into ``tools/check.sh`` -- it exits non-zero if any candidate
scores non-finite/non-positive or if the pipelined cholesky/lu schedules
stop ranking at-or-above classic (the invariant ``tests/tune`` pins
against the golden comm plans).  ``search`` runs on the REAL backend (the
point is to measure) and persists a ``tuning_cache/v1`` winner that every
subsequent ``'auto'`` resolution on the same key picks up first.

Flags: ``--n N`` (square problem size; search default 2048 on TPU / 256
on CPU, explain default 2048), ``--grid RxC``, ``--dtype NAME``,
``--machine {tpu,gpu,cpu}`` (cost-model constants override), ``--top K``
(search: how many cost-ranked candidates to measure), ``--reps R``,
``--dry-run`` (search without writing the cache).
"""
import math
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _bootstrap(force_cpu: bool) -> None:
    if _REPO not in sys.path:
        sys.path.insert(0, _REPO)
    if force_cpu:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    if force_cpu:
        jax.config.update("jax_platform_name", "cpu")
    jax.config.update("jax_num_cpu_devices", 8)


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
        raise SystemExit(f"grid {r}x{c} needs {r * c} devices, "
                         f"have {len(devs)}")
    return Grid(devs[: r * c], height=r)


def _dims(op: str, n: int):
    return (n, n, n) if op == "gemm" else (n, n)


def _fmt_cfg(cfg: dict) -> str:
    return " ".join(f"{k}={v}" for k, v in sorted(cfg.items()))


def cmd_explain(op, n, grid_spec, dtype_name, machine_name) -> int:
    import jax.numpy as jnp
    from elemental_tpu import tune
    from elemental_tpu.tune.cost_model import MACHINES
    grid = _grid(grid_spec)
    machine = MACHINES.get(machine_name) if machine_name else None
    dims = _dims(op, n)
    ctx, scored = tune.explain(op, gshape=dims, dtype=jnp.dtype(dtype_name),
                               grid=grid, machine=machine)
    mname = (machine.name if machine else ctx.backend)
    print(f"# {op} dims={tuple(dims)} dtype={ctx.dtype} "
          f"grid={ctx.grid_shape[0]}x{ctx.grid_shape[1]} "
          f"machine-model={mname}  ({len(scored)} candidates, best first)")
    print(f"{'config':42s} {'total':>10s} {'compute':>10s} {'latency':>10s} "
          f"{'bandwidth':>10s} {'rounds':>7s} {'bytes':>12s}")
    bad = 0
    for b in scored:
        t = b.total_s
        if not math.isfinite(t) or t <= 0:
            bad += 1
        print(f"{_fmt_cfg(b.config):42s} {t:10.3e} {b.compute_s:10.3e} "
              f"{b.latency_s:10.3e} {b.bandwidth_s:10.3e} {b.rounds:7.0f} "
              f"{b.comm_bytes:12.0f}")
    best = scored[0]
    print(f"chosen: {_fmt_cfg(best.config)}  "
          f"(cost model; a measured cache entry would take precedence)")
    if bad:
        print(f"SELF-CHECK FAILED: {bad} candidate(s) scored non-finite or "
              "non-positive", file=sys.stderr)
        return 1
    # pipelined-schedule invariant at the GOLDEN comm-plan geometry
    # (n=64, nb=16, tail crossover=32 -- the regime the golden snapshots
    # and tests/tune pin): lookahead+crossover must rank at or above
    # classic.  (At the displayed n the ordering may legitimately differ,
    # e.g. crossover >= n degenerates to gather-all + replicated factor.)
    if op in ("cholesky", "lu"):
        from elemental_tpu.tune import TuneContext
        from elemental_tpu.tune import cost_model as _cm
        gctx = TuneContext(op, (64, 64), "float32", ctx.grid_shape,
                           ctx.backend)

        def _score(la, xo):
            return _cm.score_config(
                op, {"nb": 16, "lookahead": la, "crossover": xo},
                ctx=gctx, grid=grid, dtype=jnp.float32, machine=machine)

        cl, xo = _score(False, 0), _score(True, 32)
        tag = (f"golden-geometry invariant (n=64 nb=16): "
               f"lookahead+crossover {xo.total_s:.3e} "
               f"({xo.prim_counts.get('all_gather', 0)} all_gathers) vs "
               f"classic {cl.total_s:.3e} "
               f"({cl.prim_counts.get('all_gather', 0)} all_gathers)")
        if xo.total_s > cl.total_s * (1 + 1e-9):
            print(f"SELF-CHECK FAILED: {tag}", file=sys.stderr)
            return 1
        print(f"self-check ok: {tag}")
    return 0


def cmd_search(op, n, grid_spec, dtype_name, top, reps, dry_run) -> int:
    import jax
    import jax.numpy as jnp
    from elemental_tpu.tune import measure
    grid = _grid(grid_spec)
    if n is None:
        on_tpu = jax.devices()[0].platform != "cpu"
        n = 2048 if on_tpu else 256
    dims = _dims(op, n)
    winner, measured, key = measure.search(
        op, dims, grid, jnp.dtype(dtype_name), top=top, reps=reps,
        write_cache=not dry_run, verbose=True)
    print(f"winner: {_fmt_cfg(winner.config)}  {winner.seconds * 1e3:.2f} ms "
          f"{winner.tflops:.3f} TFLOP/s")
    if dry_run:
        print("dry run: cache not written")
    else:
        print(f"recorded: {key.path()}")
    return 0


def cmd_show(op) -> int:
    from elemental_tpu import tune
    from elemental_tpu.obs import metrics as obs_metrics
    docs, rejects = tune.cache_scan()
    if op:
        docs = [d for d in docs if d.get("op") == op]
        rejects = [r for r in rejects if r["file"].startswith(f"{op}__")]
    print(f"# cache dir: {tune.cache_dir()}  ({len(docs)} entries, "
          f"{len(rejects)} invalid)")
    for d in docs:
        metric = d.get("metric", {})
        extra = f"  {metric.get('tflops', 0):.3f} TFLOP/s" if metric else ""
        print(f"{d['_file']:64s} {_fmt_cfg(d['config'])} "
              f"[{d.get('source', '?')}]{extra}")
    for r in rejects:
        # a schema-mismatch file used to be rejected with zero visibility;
        # now it is both printed here and counted on the metrics registry
        print(f"INVALID {r['file']:56s} ({r['reason']}; ignored by the "
              "resolver)")
    events = obs_metrics.current().counters("tune_cache_events")
    if events:
        tally: dict = {}
        for (_, labels), v in events.items():
            ev = dict(labels).get("event", "?")
            tally[ev] = tally.get(ev, 0) + v
        row = "  ".join(f"{k}={int(v)}" for k, v in sorted(tally.items()))
        print(f"# tune_cache_events (this process): {row}")
    return 0


def cmd_clear(op) -> int:
    from elemental_tpu import tune
    n = tune.clear_cache(op)
    print(f"removed {n} entr{'y' if n == 1 else 'ies'} from "
          f"{tune.cache_dir()}")
    return 0


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help"):
        print(__doc__)
        return 0
    cmd = argv.pop(0)
    if cmd not in ("search", "show", "clear", "explain"):
        print(__doc__)
        raise SystemExit(f"unknown command {cmd!r}")
    op = None
    n = None
    grid_spec = dtype_name = machine_name = None
    top, reps, dry_run = 8, 3, False
    dtype_name = "float32"
    it = iter(argv)
    for arg in it:
        if arg == "--n":
            n = int(next(it))
        elif arg == "--grid":
            grid_spec = next(it)
        elif arg == "--dtype":
            dtype_name = next(it)
        elif arg == "--machine":
            machine_name = next(it)
        elif arg == "--top":
            top = int(next(it))
        elif arg == "--reps":
            reps = int(next(it))
        elif arg == "--dry-run":
            dry_run = True
        elif arg.startswith("--"):
            raise SystemExit(f"unknown flag {arg!r}")
        else:
            op = arg
    if cmd in ("search", "explain") and op is None:
        raise SystemExit(f"{cmd} needs an op "
                         "(cholesky/lu/qr/gemm/trsm/herk)")
    _bootstrap(force_cpu=cmd != "search")
    if cmd == "explain":
        return cmd_explain(op, n if n is not None else 2048, grid_spec,
                           dtype_name, machine_name)
    if cmd == "search":
        return cmd_search(op, n, grid_spec, dtype_name, top, reps, dry_run)
    if cmd == "show":
        return cmd_show(op)
    return cmd_clear(op)


if __name__ == "__main__":
    try:
        import signal
        signal.signal(signal.SIGPIPE, signal.SIG_DFL)   # `| head` etc.
    except (ImportError, AttributeError, ValueError):
        pass
    raise SystemExit(main())
