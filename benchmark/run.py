#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name, under this directory:

    workloads/<cell>.json    -> config, traffic, chips, why
    configs/<config>.json    -> kind, operator, sizes, grid, limits
    traffic/<traffic>.json   -> the mix's parameters
    kinds/<kind>.py          -> setup(config, traffic, devices, seed)
    end_to_end/<metric>.py   -> UNIT, read(run)            (--trace 0)
    layer_metrics/<name>.py  -> LAYER, UNIT, MOVES, read(trace, run)
                                                           (--trace 1)
    peaks.json               -> device_kind -> published peaks

The harness owns the clock, the window, the comparison that decides
``correct`` and the last line.  A run that finds no TPU, or another
number of devices than the cell's ``chips``, fails and prints no result.
"""
import time

_T0 = time.perf_counter()          # process start, for setup_s

import argparse
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))

#: solves in the traced window
TRACED_SOLVES = 3


def say(**fields):
    """One earlier line of output (never the last)."""
    print(json.dumps(fields), flush=True)


def load_json(bench_dir, folder, name):
    with open(os.path.join(bench_dir, folder, name + ".json")) as f:
        return json.load(f)


def load_module(bench_dir, folder, name):
    path = os.path.join(bench_dir, folder, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{folder}_{name.replace('-', '_').replace('.', '_')}",
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def read_metrics(bench_dir, folder, *args):
    """``{name: {"value", "unit"}}`` from every reader file in a folder;
    a reader that finds nothing to read returns None and is left out."""
    names = sorted(f[:-3] for f in os.listdir(os.path.join(bench_dir, folder))
                   if f.endswith(".py"))
    metrics = {}
    for name in names:
        reader = load_module(bench_dir, folder, name)
        value = reader.read(*args)
        if value is not None:
            metrics[name] = {"value": value, "unit": reader.UNIT}
    return metrics


def resolve(bench_dir, workload):
    """cell -> (cell, config, traffic), by file name alone."""
    cell = load_json(bench_dir, "workloads", workload)
    config = load_json(bench_dir, "configs", cell["config"])
    traffic = load_json(bench_dir, "traffic", cell["traffic"])
    return cell, config, traffic


def find_devices(chips):
    """The cell's TPU devices, or SystemExit: there is no CPU branch."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"benchmark: needs a TPU, JAX found "
                         f"{devices[0].platform!r}; nothing is measured "
                         f"on another platform")
    if len(devices) != chips:
        raise SystemExit(f"benchmark: the cell asks for {chips} chips, "
                         f"JAX found {len(devices)}")
    return devices


class CompileCounter:
    """Counts compile requests (cache hits included) and cache hits."""

    def __init__(self):
        from jax import monitoring
        self.requests = self.hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, _seconds, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def enable_cache():
    """The persistent compilation cache, where the program's own rule puts
    it (``JAX_COMPILATION_CACHE_DIR`` or ``<checkout>/.jax_compile_cache``),
    for every program however small or quick to compile."""
    import jax
    from elemental_tpu.core.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


def build_session(kind, compiles, *args):
    """The session the window drives.  Where set-up had to compile a
    program afresh (it is in the cache now), the session is dropped and
    built again from the cache: in one process a freshly compiled
    hpd_solve ran every solve 1.4 % slower than a session rebuilt from
    the cache (PERF.md, PR 27), and every later run times the rebuilt
    kind."""
    import jax
    session = kind.setup(*args)
    if compiles.requests > compiles.hits:
        del session
        jax.clear_caches()
        session = kind.setup(*args)
    return session


def timed_solve(session, i, clock=time.perf_counter):
    """(seconds, checked numbers) of solve ``i``: generate untimed, the
    one call timed to ``block_until_ready`` on X, the check untimed."""
    import jax
    operands = session.prepare(i)
    t0 = clock()
    X = jax.block_until_ready(session.solve(operands))
    seconds = clock() - t0
    del operands
    return seconds, session.check(i, X)


def judge(checked, limits):
    """Solves whose compared numbers are over a limit or not finite."""
    failed = 0
    for numbers in checked:
        ok = all(math.isfinite(v) for v in numbers.values()) and all(
            numbers[name] <= lim["limit"] for name, lim in limits.items())
        failed += not ok
    return failed


def report_checks(checked, config):
    """Each number compared, its worst reading, beside its limit."""
    for name, lim in config["limits"].items():
        say(compared=name, worst=max(c[name] for c in checked),
            limit=lim["limit"], solves=len(checked))
    for name, bound in config.get("printed_only", {}).items():
        say(printed_only=name, worst=max(c[name] for c in checked),
            passes_below=bound, solves=len(checked))


def run_window(session, seconds, clock=time.perf_counter):
    """Back-to-back solves until the window has lasted ``seconds``."""
    solve_seconds, checked = [], []
    start = clock()
    while not solve_seconds or clock() - start < seconds:
        s, numbers = timed_solve(session, len(solve_seconds), clock)
        solve_seconds.append(s)
        checked.append(numbers)
    return solve_seconds, checked, clock() - start


def run_traced(session):
    """A window of ``TRACED_SOLVES`` solves under the profiler; the trace
    goes to a temporary directory and is reduced and deleted."""
    import jax
    import xplane
    trace_dir = tempfile.mkdtemp(prefix="benchmark_trace_")
    try:
        jax.profiler.start_trace(trace_dir)
        try:
            results = [timed_solve(session, i) for i in range(TRACED_SOLVES)]
        finally:
            jax.profiler.stop_trace()
        planes = xplane.read_xplane(xplane.find_xplane(trace_dir))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    trace = xplane.reduce_trace(planes, session.facts["solve_module"])
    return [r[0] for r in results], [r[1] for r in results], trace, planes


def device_record(devices):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": max(peaks)}


def breakdown(trace):
    """Top device ops and longest idle gaps of the timed windows, on the
    first device."""
    import xplane
    d = trace["devices"][min(trace["devices"])]
    return {"device_ops": xplane.top_names(d["timed_ops"], 10),
            "idle_gaps": xplane.gaps(d["timed_ops"], d["windows"], 5)}


def main(argv=None, bench_dir=HERE, devices=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = parser.parse_args(argv)

    cell, config, traffic = resolve(bench_dir, args.workload)
    sys.path[:0] = [bench_dir, os.path.dirname(HERE)]
    if devices is None:
        devices = find_devices(cell["chips"])
    cache_dir = enable_cache()
    with open(os.path.join(bench_dir, "peaks.json")) as f:
        peak = json.load(f)[devices[0].device_kind]   # unknown kind: error

    compiles = CompileCounter()
    kind = load_module(bench_dir, "kinds", config["kind"])
    session = build_session(kind, compiles, config, traffic, devices,
                            args.seed)
    setup_s = time.perf_counter() - _T0
    requests_at_setup = compiles.requests
    say(workload=args.workload, seed=args.seed, cache_dir=cache_dir,
        setup_compile_requests=compiles.requests,
        setup_cache_hits=compiles.hits, warm_check=session.warm,
        **session.facts)

    run = {"facts": session.facts, "setup_s": setup_s, "peak": peak}
    if args.trace:
        _seconds, checked, trace, _planes = run_traced(session)
        metrics = read_metrics(bench_dir, "layer_metrics", trace, run)
        devs = trace["devices"].values()
        extra = {"busy_s": sum(d["busy_s"] for d in devs) / len(devs),
                 "window_s": max(d["window_s"] for d in devs)}
    else:
        seconds, checked, window_s = run_window(session, args.seconds)
        run["solve_seconds"] = seconds
        metrics = read_metrics(bench_dir, "end_to_end", run)
        extra = {}
        say(window_s=window_s, solves=len(seconds), solve_s_min=min(seconds),
            solve_s_max=max(seconds))
    in_window = compiles.requests - requests_at_setup
    say(compile_requests_in_window=in_window)
    report_checks(checked, config)
    failed = judge(checked, config["limits"])

    line = {"correct": failed == 0 and in_window == 0,
            "attempted": len(checked), "failed": failed,
            "metrics": metrics,
            "device": {**device_record(devices), **extra}}
    if args.trace:
        line["breakdown"] = breakdown(trace)
    print(json.dumps(line), flush=True)
    return line


if __name__ == "__main__":
    main()
