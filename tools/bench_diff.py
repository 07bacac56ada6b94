#!/usr/bin/env python3
"""Bench regression gate: compare a bench run against the BENCH_r*.json
trajectory and exit non-zero on regression (ISSUE 5).

The repo records one ``BENCH_rNN.json`` per PR round (the driver wraps
``bench.py``'s single JSON line under a ``"parsed"`` key).  This tool
makes that trajectory a GATE instead of an archive::

    python tools/bench_diff.py --check BENCH_r05.json
        # BENCH_r05 vs the best of BENCH_r01..r04 (same directory,
        # lower round index); exit 1 if any gated metric regressed
        # more than the threshold
    python tools/bench_diff.py current.json BENCH_r04.json BENCH_r03.json
        # explicit current-vs-baselines comparison (current.json may be
        # the wrapped form or a raw bench.py output line)

Gated metrics default to the ROOFLINE-NORMALIZED ratios ``vs_baseline``
(cholesky), ``lu_vs_baseline`` and ``gemm_vs_baseline`` (the ISSUE-16
tall-skinny GEMM headline, whose named value
``gemm_tall_skinny_tflops_per_chip`` is gated on the same wide band as
the LU TFLOP/s) -- raw TFLOP/s moves with the chip's clocks and
neighbours, while the in-run-roofline ratio isolates algorithmic
regressions.  Override with one
or more ``--metric NAME`` (e.g. ``--metric value`` for raw cholesky
TFLOP/s, ``--metric lu_value``).

Thresholds: ``--threshold 0.10`` sets the global relative-drop tolerance
(default 10%); ``--threshold NAME=X`` pins a per-metric override (both
forms may repeat; built-in per-metric defaults live in
:data:`DEFAULT_PER_METRIC`).  A metric regresses when

    current < (1 - threshold) * max(baselines)

i.e. the gate compares against the BEST recorded value, so a slow decay
across rounds cannot ratchet the bar down.  Latency-style metrics listed
in :data:`LOWER_IS_BETTER` (the ``bench_serve.py`` percentiles, ISSUE 9)
invert: best is the MINIMUM baseline and a regression is
``current > (1 + threshold) * best`` -- so ``serve_p99_ms`` and
``serve_solves_per_sec`` (plus their ``serve_async_*`` twins from the
ISSUE-14 pipelined front, and the windowed worst-per-tenant
``serve_slo_p99_ms`` from the ISSUE-20 SLO monitor) gate serving
latency/throughput alongside the TFLOP/s headlines.  Nested documents under the
``"obs"`` key (the ``obs_bench/v1`` trail, including ISSUE 8's
``redist_wire_bytes`` total) are accepted and surfaced as informational
lines, never gated -- byte estimates are schedule properties, not
chip-weather measurements.  The one exception (ISSUE 13) is the
MEASURED one-shot redistribution rate: :func:`load_doc` promotes
``obs.redist_p2p_gbps.direct`` to a top-level ``redist_p2p_gbps`` key
gated alongside the TFLOP/s headlines (wide 40% band -- interconnect
microbenchmarks swing with fabric weather; zero-rate 1x1 runs are
skipped, not compared).  Metrics absent from the
current run or from every baseline are skipped with a note (older rounds
predate some metrics) -- which is also how METRIC RENAMES stay
false-positive-free: the bench names its headline values
(``"metric"``/``"lu_metric"``), :func:`load_doc` promotes them to
top-level keys (``doc[doc["lu_metric"]] = doc["lu_value"]``), and a
renamed metric (e.g. ``lu_n16384_...`` -> ``lu_n32768_...`` when ISSUE 6
raised the LU headline to N=32768) simply has no baseline until the next
round records one.  Stdlib-only: no jax import, safe anywhere.
"""
from __future__ import annotations

import glob
import json
import os
import re
import sys

DEFAULT_METRICS = ("vs_baseline", "lu_vs_baseline",
                   "lu_n32768_tflops_per_chip",
                   "gemm_vs_baseline",
                   "gemm_tall_skinny_tflops_per_chip",
                   "serve_p99_ms", "serve_solves_per_sec",
                   "serve_async_p99_ms", "serve_async_solves_per_sec",
                   "serve_fleet_p99_ms", "serve_fleet_solves_per_sec",
                   "serve_slo_p99_ms",
                   "redist_p2p_gbps")
DEFAULT_THRESHOLD = 0.10

#: built-in per-metric thresholds (user ``--threshold NAME=X`` overrides).
#: Raw TFLOP/s metrics move with the chip's clocks and neighbours, so
#: the named LU headline gets a wider band than the
#: roofline-normalized default ratios; serving wall-clock metrics swing
#: with host weather and get the same wide band.
DEFAULT_PER_METRIC = {"lu_n32768_tflops_per_chip": 0.25,
                      "gemm_tall_skinny_tflops_per_chip": 0.25,
                      "serve_p99_ms": 0.25,
                      "serve_solves_per_sec": 0.25,
                      "serve_async_p99_ms": 0.25,
                      "serve_async_solves_per_sec": 0.25,
                      "serve_fleet_p99_ms": 0.25,
                      "serve_fleet_solves_per_sec": 0.25,
                      "serve_slo_p99_ms": 0.25,
                      "redist_p2p_gbps": 0.40}

#: metrics where SMALLER is better (latency percentiles from
#: bench_serve.py): the gate inverts -- best baseline is the MINIMUM and
#: a regression is ``current > (1 + threshold) * best``.
LOWER_IS_BETTER = {"serve_p50_ms", "serve_p99_ms",
                   "serve_async_p50_ms", "serve_async_p99_ms",
                   "serve_fleet_p50_ms", "serve_fleet_p99_ms",
                   "serve_slo_p99_ms"}

_ROUND_RE = re.compile(r"_r(\d+)\.json$")


def load_doc(path: str) -> dict:
    """The bench metric dict of one file (unwraps the driver's record).

    Named headline values are promoted to top-level keys so per-metric
    gating/thresholds address them by their bench-assigned names (which
    carry the problem size, e.g. ``lu_n32768_tflops_per_chip``)."""
    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc, dict) and isinstance(doc.get("parsed"), dict):
        doc = doc["parsed"]
    if not isinstance(doc, dict):
        raise ValueError(f"{path}: not a JSON object")
    for prefix in ("", "lu_", "gemm_"):
        name, val = doc.get(prefix + "metric"), doc.get(prefix + "value")
        if isinstance(name, str) and isinstance(val, (int, float)) \
                and name not in doc:
            doc[name] = val
    # the measured one-shot redistribution rate joins the gated set
    # (ISSUE 13): a zero rate means a 1x1/no-wire run -- skip it so a
    # single-chip round cannot poison the baseline or fail the gate
    obs = doc.get("obs")
    if isinstance(obs, dict) and "redist_p2p_gbps" not in doc:
        p2p = obs.get("redist_p2p_gbps")
        if isinstance(p2p, dict) and isinstance(p2p.get("direct"),
                                                (int, float)) \
                and p2p["direct"] > 0:
            doc["redist_p2p_gbps"] = p2p["direct"]
    return doc


def round_index(path: str):
    m = _ROUND_RE.search(os.path.basename(path))
    return int(m.group(1)) if m else None


def trajectory_before(path: str) -> list:
    """Sibling BENCH_r*.json files with a strictly lower round index."""
    idx = round_index(path)
    if idx is None:
        raise SystemExit(f"--check {path}: expected a *_rNN.json filename")
    d = os.path.dirname(os.path.abspath(path))
    out = []
    for cand in sorted(glob.glob(os.path.join(d, "BENCH_r*.json"))):
        ci = round_index(cand)
        if ci is not None and ci < idx:
            out.append(cand)
    return out


def compare(current: dict, baselines: list, metrics, thresholds) -> list:
    """[(metric, current, best, baseline_file, threshold, regressed)] for
    every gated metric comparable on both sides."""
    rows = []
    for name in metrics:
        cur = current.get(name)
        if not isinstance(cur, (int, float)):
            continue
        lower = name in LOWER_IS_BETTER
        best, src = None, None
        for path, doc in baselines:
            v = doc.get(name)
            if isinstance(v, (int, float)) and (
                    best is None or (v < best if lower else v > best)):
                best, src = v, path
        if best is None:
            continue
        thr = thresholds.get(name, thresholds.get(None, DEFAULT_THRESHOLD))
        regressed = cur > (1.0 + thr) * best if lower \
            else cur < (1.0 - thr) * best
        rows.append((name, cur, best, src, thr, regressed))
    return rows


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help"):
        print(__doc__)
        return 0
    check = None
    paths = []
    metrics: list = []
    thresholds: dict = {None: DEFAULT_THRESHOLD, **DEFAULT_PER_METRIC}
    it = iter(argv)
    for arg in it:
        if arg == "--check":
            check = next(it)
        elif arg == "--metric":
            metrics.append(next(it))
        elif arg == "--threshold":
            v = next(it)
            if "=" in v:
                name, x = v.split("=", 1)
                thresholds[name] = float(x)
            else:
                thresholds[None] = float(v)
        elif arg.startswith("--"):
            raise SystemExit(f"unknown flag {arg!r}")
        else:
            paths.append(arg)
    if check is not None:
        current_path = check
        baseline_paths = trajectory_before(check)
    else:
        if len(paths) < 2:
            raise SystemExit("need --check FILE or CURRENT BASELINE...")
        current_path, baseline_paths = paths[0], paths[1:]
    current = load_doc(current_path)
    baselines = [(p, load_doc(p)) for p in baseline_paths]
    if not baselines:
        print(f"bench_diff: no baselines before {current_path}; nothing to gate")
        return 0
    gated = metrics or list(DEFAULT_METRICS)
    rows = compare(current, baselines, gated, thresholds)
    print(f"# current: {current_path}   baselines: "
          f"{', '.join(os.path.basename(p) for p in baseline_paths)}")
    obs = current.get("obs")
    if isinstance(obs, dict) \
            and isinstance(obs.get("redist_wire_bytes"), (int, float)):
        logical = obs.get("redist_bytes")
        note = ""
        if isinstance(logical, (int, float)) and logical:
            note = f"  (logical {logical}, " \
                   f"{logical / max(obs['redist_wire_bytes'], 1):.2f}x)"
        print(f"# redist_wire_bytes: {obs['redist_wire_bytes']}{note}")
    print(f"{'metric':20s} {'current':>10s} {'best':>10s} {'delta':>8s} "
          f"{'thresh':>7s}  {'best from'}")
    failed = 0
    for name, cur, best, src, thr, regressed in rows:
        delta = (cur - best) / best if best else 0.0
        flag = "  REGRESSION" if regressed else ""
        print(f"{name:20s} {cur:10.4f} {best:10.4f} {delta:+7.1%} "
              f"{thr:7.0%}  {os.path.basename(src)}{flag}")
        failed += bool(regressed)
    skipped = [m for m in gated if m not in {r[0] for r in rows}]
    if skipped:
        print(f"# skipped (absent on one side): {', '.join(skipped)}")
    if not rows:
        print("bench_diff: no comparable metrics; nothing gated")
        return 0
    if failed:
        print(f"bench_diff: {failed} metric(s) regressed beyond threshold",
              file=sys.stderr)
        return 1
    print("bench_diff: ok")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
