"""Comm-plan audit CLI: extract / pin / lint driver collective schedules.

The command-line face of ``elemental_tpu/analysis`` (ISSUE 3).  Traces
registered distributed drivers abstractly (no device execution; forces an
8-virtual-device CPU backend, so it runs anywhere) and works with the
``comm_plan/v1`` JSON documents:

    python -m perf.comm_audit audit cholesky           # print plans (all
                                                       #   cholesky_* x grids)
    python -m perf.comm_audit audit lu_classic --grid 2x2 --events
    python -m perf.comm_audit audit --all
    python -m perf.comm_audit diff                     # all drivers vs the
                                                       #   golden snapshots
    python -m perf.comm_audit diff cholesky --update-golden
    python -m perf.comm_audit lint --all               # rule-based lints;
                                                       #   exit 1 on findings
    python -m perf.comm_audit lint --all --fix-hint    # + print each
                                                       #   finding's rewrite

Memory-plan twins (ISSUE 18) of the three commands work with the
``memory_plan/v1`` documents (per-device peak live bytes, high-water
timeline, replicated-materialization census) and the EL006-EL009 rules:

    python -m perf.comm_audit mem cholesky             # print memory plans
    python -m perf.comm_audit mem-diff                 # all drivers vs
                                                       #   tests/golden/memory_plans/
    python -m perf.comm_audit mem-diff --update-golden
    python -m perf.comm_audit mem-lint --all           # EL006-EL009; exit 1
                                                       #   on findings

``diff``/``mem-diff`` exit non-zero when any plan deviates from its
golden snapshot under ``tests/golden/comm_plans/`` /
``tests/golden/memory_plans/`` (regenerate with ``--update-golden``
after an INTENTIONAL schedule change and review the diff like any other
code change); ``lint``/``mem-lint`` exit non-zero on any finding.
``tools/check.sh`` runs them as the pre-commit gate (``static`` gate for
the memory side).

A driver name selects by exact match or prefix: ``audit cholesky`` covers
``cholesky_classic`` / ``cholesky_lookahead`` / ``cholesky_crossover``.
"""
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GOLDEN_DIR = os.path.join(_REPO, "tests", "golden", "comm_plans")
MEM_GOLDEN_DIR = os.path.join(_REPO, "tests", "golden", "memory_plans")

#: grids every audit runs on: the degenerate single device and the
#: smallest genuinely 2-D grid (both redistribution regimes)
GRIDS = ((1, 1), (2, 2))


def _bootstrap():
    """CPU backend with 8 virtual devices, BEFORE jax initializes."""
    if _REPO not in sys.path:
        sys.path.insert(0, _REPO)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    jax.config.update("jax_platform_name", "cpu")
    # match the test harness (tests/conftest.py): the comm plans are
    # x64-invariant (their goldens pass in both modes) but the MEMORY
    # plans are not -- integer pivot avals double under x64 -- so the
    # CLI must trace in the same mode the golden gate tests run in
    jax.config.update("jax_enable_x64", True)
    jax.config.update("jax_num_cpu_devices", 8)


def _grid(r: int, c: int):
    import jax
    from elemental_tpu.core.grid import Grid
    return Grid(jax.devices()[: r * c], height=r)


def _select(name: str | None) -> list:
    from elemental_tpu import analysis as an
    names = an.driver_names()
    if name is None or name == "--all":
        return names
    if name in names:
        return [name]
    picked = [d for d in names if d.startswith(name)]
    if not picked:
        raise SystemExit(f"unknown driver {name!r}; known: {names}")
    return picked


def golden_path(driver: str, grid) -> str:
    return os.path.join(GOLDEN_DIR, f"{driver}__{grid[0]}x{grid[1]}.json")


def mem_golden_path(driver: str, grid) -> str:
    return os.path.join(MEM_GOLDEN_DIR,
                        f"{driver}__{grid[0]}x{grid[1]}.json")


def _trace(driver: str, grid, n=None, nb=None):
    from elemental_tpu import analysis as an
    kwargs = {}
    if n is not None:
        kwargs["n"] = n
    if nb is not None:
        kwargs["nb"] = nb
    return an.trace_driver(driver, _grid(*grid), **kwargs)


def cmd_audit(drivers, grids, n, nb, events: bool) -> int:
    for driver in drivers:
        for grid in grids:
            plan, _, _ = _trace(driver, grid, n, nb)
            print(plan.to_json(events=events))
    return 0


def cmd_diff(drivers, grids, n, nb, update: bool) -> int:
    from elemental_tpu.analysis import golden_doc, diff_docs
    bad = 0
    for driver in drivers:
        for grid in grids:
            plan, _, _ = _trace(driver, grid, n, nb)
            doc = golden_doc(plan)
            path = golden_path(driver, grid)
            tag = f"{driver} {grid[0]}x{grid[1]}"
            if update:
                os.makedirs(GOLDEN_DIR, exist_ok=True)
                with open(path, "w") as f:
                    json.dump(doc, f, indent=1, sort_keys=False)
                    f.write("\n")
                print(f"updated {tag}: {os.path.relpath(path, _REPO)}")
                continue
            if not os.path.exists(path):
                print(f"MISSING golden for {tag} ({path}); "
                      f"run with --update-golden")
                bad += 1
                continue
            with open(path) as f:
                golden = json.load(f)
            lines = diff_docs(golden, doc)
            if lines:
                bad += 1
                print(f"DIFF {tag}:")
                for ln in lines:
                    print(f"  {ln}")
            else:
                print(f"ok {tag}")
    return 1 if bad else 0


def cmd_lint(drivers, grids, n, nb, fix_hint: bool = False) -> int:
    from elemental_tpu.analysis import lint_plan
    total = 0
    for driver in drivers:
        for grid in grids:
            plan, closed, log = _trace(driver, grid, n, nb)
            findings = lint_plan(plan, log, closed)
            for f in findings:
                print(f"{driver} {grid[0]}x{grid[1]}: {f}")
                if fix_hint and f.fix_hint:
                    print(f"  fix: {f.fix_hint}")
            total += len(findings)
    print(f"{total} finding(s)")
    return 1 if total else 0


def _trace_mem(driver: str, grid, n=None, nb=None):
    from elemental_tpu.analysis import trace_memory
    return trace_memory(driver, _grid(*grid), n=n, nb=nb)


def cmd_mem(drivers, grids, n, nb) -> int:
    for driver in drivers:
        for grid in grids:
            mplan, _, _ = _trace_mem(driver, grid, n, nb)
            print(mplan.to_json())
    return 0


def cmd_mem_diff(drivers, grids, n, nb, update: bool) -> int:
    from elemental_tpu.analysis import golden_mem_doc, diff_mem_docs
    bad = 0
    for driver in drivers:
        for grid in grids:
            mplan, _, _ = _trace_mem(driver, grid, n, nb)
            doc = golden_mem_doc(mplan)
            path = mem_golden_path(driver, grid)
            tag = f"{driver} {grid[0]}x{grid[1]}"
            if update:
                os.makedirs(MEM_GOLDEN_DIR, exist_ok=True)
                with open(path, "w") as f:
                    json.dump(doc, f, indent=1, sort_keys=False)
                    f.write("\n")
                print(f"updated {tag}: {os.path.relpath(path, _REPO)}")
                continue
            if not os.path.exists(path):
                print(f"MISSING memory golden for {tag} ({path}); "
                      f"run with --update-golden")
                bad += 1
                continue
            with open(path) as f:
                golden = json.load(f)
            lines = diff_mem_docs(golden, doc)
            if lines:
                bad += 1
                print(f"DIFF {tag}:")
                for ln in lines:
                    print(f"  {ln}")
            else:
                print(f"ok {tag}")
    return 1 if bad else 0


def cmd_mem_lint(drivers, grids, n, nb, fix_hint: bool = False) -> int:
    from elemental_tpu.analysis import lint_memory
    total = 0
    for driver in drivers:
        for grid in grids:
            mplan, closed, log = _trace_mem(driver, grid, n, nb)
            findings = lint_memory(mplan, log, closed)
            for f in findings:
                print(f"{driver} {grid[0]}x{grid[1]}: {f}")
                if fix_hint and f.fix_hint:
                    print(f"  fix: {f.fix_hint}")
            total += len(findings)
    print(f"{total} finding(s)")
    return 1 if total else 0


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help"):
        print(__doc__)
        return 0
    cmd = argv.pop(0)
    if cmd not in ("audit", "diff", "lint", "mem", "mem-diff", "mem-lint"):
        print(__doc__)
        raise SystemExit(f"unknown command {cmd!r}")
    _bootstrap()
    name = None
    grids = list(GRIDS)
    n = nb = None
    events = update = fix_hint = False
    it = iter(argv)
    for arg in it:
        if arg == "--grid":
            r, c = next(it).split("x")
            grids = [(int(r), int(c))]
        elif arg == "--n":
            n = int(next(it))
        elif arg == "--nb":
            nb = int(next(it))
        elif arg == "--events":
            events = True
        elif arg == "--update-golden":
            update = True
        elif arg == "--fix-hint":
            fix_hint = True
        elif arg == "--all":
            name = None
        elif arg.startswith("--"):
            raise SystemExit(f"unknown flag {arg!r}")
        else:
            name = arg
    drivers = _select(name)
    if cmd == "audit":
        return cmd_audit(drivers, grids, n, nb, events)
    if cmd == "diff":
        return cmd_diff(drivers, grids, n, nb, update)
    if cmd == "mem":
        return cmd_mem(drivers, grids, n, nb)
    if cmd == "mem-diff":
        return cmd_mem_diff(drivers, grids, n, nb, update)
    if cmd == "mem-lint":
        return cmd_mem_lint(drivers, grids, n, nb, fix_hint)
    return cmd_lint(drivers, grids, n, nb, fix_hint)


if __name__ == "__main__":
    try:
        import signal
        signal.signal(signal.SIGPIPE, signal.SIG_DFL)   # `| head` etc.
    except (ImportError, AttributeError, ValueError):
        pass
    raise SystemExit(main())
