#!/usr/bin/env bash
# One-shot pre-commit gate (ISSUE 3 + 4 + 5 + 6 + 7 + 9): style lint +
# comm-plan lint + golden comm-plan diff + autotuner cost-model
# self-check + the tier-1 tests/tune subset + the calu/tsqr lapack gate
# (comm lint/diff on the lu/qr variants, golden-coverage check, lu/qr
# tests) + the observability smoke (perf.trace run on a tiny 1x1
# problem) + the
# resilience gate (certified-solve smoke on 1x1 + 2x2 grids incl. an
# injected fault, and the fault-injection/health test suite).  Run
# from anywhere; exits non-zero on ANY finding.  Future PRs run this
# before committing -- style/comm/explain are the cheap static slice (no
# device execution); the tune/obs/resilience tests execute small
# factorizations on the virtual-CPU mesh (~a minute warm); the full test
# suite stays `python -m pytest tests/ -m 'not slow'`.
#
#   tools/check.sh            # everything
#   tools/check.sh style      # ruff (or the stdlib fallback) only
#   tools/check.sh comm       # comm-plan lint + golden diff + the
#                             #   quantized-collective gate (codec tests,
#                             #   *_commq golden byte-ratio pins, and the
#                             #   certified-solve smoke whose first rung
#                             #   runs int8 wire precision)
#   tools/check.sh tune       # cost-model self-check + tests/tune only
#   tools/check.sh obs        # perf.trace smoke + the ISSUE-20 fleet
#                             #   telemetry smoke (perf.trace serve
#                             #   --smoke: lifecycle timelines, SLO
#                             #   snapshot, flight-record replay) +
#                             #   tests/obs
#   tools/check.sh lapack     # calu/tsqr gate: lu/qr comm lint + golden diff,
#                             #   golden-coverage check, lapack lu/qr tests
#   tools/check.sh resilience # certified-solve smoke (1x1 + 2x2, CPU-safe)
#                             #   + tests/resilience fault/health suite
#   tools/check.sh serve      # solver-service gate (ISSUE 9): serve smoke
#                             #   on 1x1 + 2x2, the chaos acceptance
#                             #   matrix ({bitflip,scale,nan} x
#                             #   {redistribute,compute} x {oneshot,
#                             #   persistent} + the qr op column), and
#                             #   tests/serve
#   tools/check.sh fleet      # solver-fleet gate (ISSUE 19): fleet-smoke
#                             #   (pipelined multi-grid routing, tenant
#                             #   quota rejects, grid-loss + saturation
#                             #   chaos cells with replay) and the fleet
#                             #   scheduler/routing/fairness/chaos tests
#   tools/check.sh abft       # ABFT gate (ISSUE 11): checksum-guarded
#                             #   lu/cholesky smoke (clean 1x1 + 2x2, zero
#                             #   violations; injected faults recovered at
#                             #   panel granularity, recompute count == 1)
#                             #   + the *_abft comm-plan golden diff +
#                             #   tests/resilience/test_abft.py
#   tools/check.sh gemm       # slicing-gemm gate (ISSUE 16): the
#                             #   gemm_slice comm-plan goldens (1x1 +
#                             #   2x2), the comm_audit gemm-prefix
#                             #   lint/diff coverage, the tuner-selection
#                             #   pins (auto->slice on tall-skinny 2x4,
#                             #   auto->dot on 1x1), and the slice
#                             #   correctness/plan/knob test files
#   tools/check.sh kernels    # fused-panel gate (ISSUE 17): pallas panel
#                             #   smoke (interpret-mode lu/cholesky/qr on
#                             #   1x1 + 2x2, pivot-identical LU), the
#                             #   comm-plan byte-invariance sweep under
#                             #   panel_impl='pallas', and tests/kernels
#   tools/check.sh static     # the one-stop static slice (ISSUE 18): ruff
#                             #   (or pyflakes_lite), comm-plan lint, the
#                             #   memory-plan lint (EL006-EL009: peak
#                             #   budgets, VMEM gate cross-check, missing
#                             #   donation, double materialization), the
#                             #   golden memory-plan diff, and the
#                             #   registry-driven golden-coverage check
#                             #   over BOTH golden families -- no device
#                             #   execution anywhere
#   tools/check.sh redist     # one-shot redistribution gate (ISSUE 12 +
#                             #   13): plan-compiler unit + direct-vs-
#                             #   chain bit-equivalence tests (incl.
#                             #   nonzero alignments), the LOUD
#                             #   LEGAL_PAIRS^2 coverage check, the
#                             #   *_direct comm-plan golden diffs (gemm
#                             #   round wins + qr_lq/trsm_r/herk wins +
#                             #   the redist_md ragged byte drop), the
#                             #   redist_path knob + measured-constants
#                             #   tests, the EL002 rewrite-hint smoke,
#                             #   and redist_bench --smoke
set -u
cd "$(dirname "$0")/.."

what="${1:-all}"
rc=0

if [ "$what" = "all" ] || [ "$what" = "style" ]; then
    echo "== style lint =="
    if command -v ruff >/dev/null 2>&1; then
        ruff check . || rc=1
    else
        # container images without ruff: the stdlib AST fallback covers
        # the highest-signal subset of the configured rules
        python tools/pyflakes_lite.py || rc=1
    fi
fi

if [ "$what" = "all" ] || [ "$what" = "comm" ]; then
    echo "== comm-plan lint =="
    python -m perf.comm_audit lint --all || rc=1
    echo "== golden comm-plan diff =="
    python -m perf.comm_audit diff --all || rc=1
    echo "== quantized-collective golden diff (*_commq variants) =="
    python -m perf.comm_audit diff lu_calu_commq || rc=1
    python -m perf.comm_audit diff cholesky_lookahead_commq || rc=1
    echo "== quantization codec + comm_precision tier-1 tests =="
    python -m pytest tests/core/test_comm_precision.py \
        tests/analysis/test_comm_precision_plan.py \
        -q -m 'not slow' -p no:cacheprovider || rc=1
    echo "== certified-solve smoke (quantized first rung) =="
    JAX_PLATFORMS=cpu python -m perf.certify smoke || rc=1
fi

if [ "$what" = "all" ] || [ "$what" = "tune" ]; then
    echo "== autotuner cost-model self-check =="
    # trace-only: exits non-zero if any candidate scores non-finite or the
    # golden-geometry lookahead+crossover <= classic invariant breaks
    python -m perf.tune explain cholesky || rc=1
    echo "== tune tier-1 tests =="
    python -m pytest tests/tune -q -m 'not slow' -p no:cacheprovider || rc=1
fi

if [ "$what" = "all" ] || [ "$what" = "lapack" ]; then
    echo "== calu/tsqr comm-plan lint + golden diff (lu + qr variants) =="
    python -m perf.comm_audit lint lu || rc=1
    python -m perf.comm_audit lint qr || rc=1
    python -m perf.comm_audit diff lu || rc=1
    python -m perf.comm_audit diff qr || rc=1
    echo "== golden coverage: every registered driver variant has snapshots =="
    # registry-driven, both golden families (comm_plan + memory_plan);
    # replaces the old per-gate heredoc copies (ISSUE 18 satellite)
    python tools/golden_coverage.py || rc=1
    echo "== lapack calu/tsqr tier-1 tests =="
    python -m pytest tests/lapack/test_lu.py tests/lapack/test_lu_calu.py \
        tests/lapack/test_qr.py tests/lapack/test_qr_tsqr.py \
        -q -m 'not slow' -p no:cacheprovider || rc=1
fi

if [ "$what" = "all" ] || [ "$what" = "obs" ]; then
    echo "== perf.trace smoke (tiny n, 1x1 grid, CPU-safe) =="
    JAX_PLATFORMS=cpu python -m perf.trace run cholesky --n 64 --nb 16 \
        --grid 1x1 --out /tmp/el_trace_smoke.json >/dev/null || rc=1
    echo "== perf.trace serve smoke (fleet lifecycle + SLO + flight, ISSUE 20) =="
    # self-checking: complete timelines, flow-linked export with >= 2
    # grid-worker tracks, per-tenant SLO snapshot, and a bit-identical
    # flight-record replay of the grid-loss chaos cell
    JAX_PLATFORMS=cpu python -m perf.trace serve --smoke \
        --out /tmp/el_serve_trace_smoke.json >/dev/null || rc=1
    echo "== obs tier-1 tests =="
    python -m pytest tests/obs -q -m 'not slow' -p no:cacheprovider || rc=1
fi

if [ "$what" = "all" ] || [ "$what" = "resilience" ]; then
    echo "== certified-solve smoke (lu + hpd, 1x1 + 2x2 grids, CPU-safe) =="
    # clean runs must certify; a one-shot injected fault must be repaired
    # by the escalation ladder; persistent corruption must be SURFACED
    JAX_PLATFORMS=cpu python -m perf.certify smoke || rc=1
    echo "== resilience tier-1 tests (fault injection + health + certify) =="
    python -m pytest tests/resilience -q -m 'not slow' -p no:cacheprovider || rc=1
fi

if [ "$what" = "all" ] || [ "$what" = "abft" ]; then
    echo "== abft smoke (guarded lu + cholesky + qr, clean + injected, CPU-safe) =="
    # clean guarded runs: zero violations, zero recomputes; a windowed
    # one-shot fault must be detected AT the injected panel and repaired
    # by exactly ONE panel re-execution (qr's injected kind is a bitflip,
    # the class only the ISSUE-15 checksums catch)
    JAX_PLATFORMS=cpu python -m perf.abft smoke || rc=1
    echo "== abft comm-plan goldens (lu_abft / cholesky_abft / qr_abft, 1x1 + 2x2) =="
    JAX_PLATFORMS=cpu python -m perf.comm_audit diff lu_abft || rc=1
    JAX_PLATFORMS=cpu python -m perf.comm_audit diff cholesky_abft || rc=1
    JAX_PLATFORMS=cpu python -m perf.comm_audit diff qr_abft || rc=1
    echo "== abft tier-1 tests (detection/recovery acceptance matrix) =="
    python -m pytest tests/resilience/test_abft.py -q -m 'not slow' -p no:cacheprovider || rc=1
fi

if [ "$what" = "all" ] || [ "$what" = "redist" ]; then
    echo "== one-shot plan compiler + direct-vs-chain equivalence tests =="
    python -m pytest tests/core/test_redist_direct.py \
        tests/analysis/test_direct_plan.py \
        tests/tune/test_redist_path_knob.py \
        tests/tune/test_redist_constants.py \
        -q -m 'not slow' -p no:cacheprovider || rc=1
    echo "== LEGAL_PAIRS^2 plan coverage (compile_plan total on 2x2) =="
    # fail LOUDLY on any legal endpoint pair the compiler cannot plan
    # (ISSUE 13 closed the matrix: MD/CIRC endpoints included) -- a new
    # Dist or pair added without plan support would otherwise only
    # surface as a silent chain fallback at runtime
    python - <<'PY' || rc=1
import os, sys
sys.path.insert(0, os.getcwd())
from elemental_tpu.core.dist import LEGAL_PAIRS
from elemental_tpu.redist.plan import compile_plan
missing = []
for src in LEGAL_PAIRS:
    for dst in LEGAL_PAIRS:
        if src == dst:
            continue
        if compile_plan(src, dst, (6, 5), (2, 2)) is None:
            missing.append(f"{src} -> {dst}")
if missing:
    print("compile_plan returned None for LEGAL endpoint pair(s):")
    for m in missing:
        print(f"  {m}")
    sys.exit(1)
print(f"plan coverage ok ({len(LEGAL_PAIRS)}^2 endpoint pairs on 2x2)")
PY
    echo "== *_direct comm-plan goldens (one-shot wins, 1x1 + 2x2) =="
    JAX_PLATFORMS=cpu python -m perf.comm_audit diff gemm_a_direct || rc=1
    JAX_PLATFORMS=cpu python -m perf.comm_audit diff gemm_b_direct || rc=1
    JAX_PLATFORMS=cpu python -m perf.comm_audit diff gemm_dot_direct || rc=1
    JAX_PLATFORMS=cpu python -m perf.comm_audit diff qr_lq_direct || rc=1
    JAX_PLATFORMS=cpu python -m perf.comm_audit diff trsm_r_direct || rc=1
    JAX_PLATFORMS=cpu python -m perf.comm_audit diff herk_direct || rc=1
    JAX_PLATFORMS=cpu python -m perf.comm_audit diff redist_md_direct || rc=1
    echo "== EL002 rewrite-hint smoke (lint --fix-hint accepted, clean) =="
    JAX_PLATFORMS=cpu python -m perf.comm_audit lint gemm --fix-hint || rc=1
    echo "== redist_bench smoke (1x1, chain-vs-direct bit-match) =="
    JAX_PLATFORMS=cpu python -m perf.redist_bench --smoke --reps 1 \
        > /dev/null || rc=1
fi

if [ "$what" = "all" ] || [ "$what" = "gemm" ]; then
    echo "== gemm_slice comm-plan goldens (1x1 + 2x2) =="
    JAX_PLATFORMS=cpu python -m perf.comm_audit diff gemm_slice || rc=1
    echo "== comm_audit gemm-prefix coverage (lint + diff over all gemm variants) =="
    JAX_PLATFORMS=cpu python -m perf.comm_audit lint gemm || rc=1
    JAX_PLATFORMS=cpu python -m perf.comm_audit diff gemm || rc=1
    echo "== tuner-selection pins (auto->slice tall-skinny 2x4, auto->dot 1x1) =="
    # resolve on the comm_audit virtual-device mesh: slice must win the
    # tall-skinny geometry on a 2x4 grid and the pinned dot early-out
    # must keep the 1x1 tie-break (slice joining the space is additive)
    python - <<'PY' || rc=1
import os, sys
sys.path.insert(0, os.getcwd())
from perf.comm_audit import _bootstrap
_bootstrap()
import jax
import jax.numpy as jnp
import elemental_tpu as el
from elemental_tpu import tune

def pick(gshape, r, c):
    grid = el.Grid(jax.devices()[: r * c], height=r)
    kn = tune.resolve_knobs("gemm", gshape=gshape, dtype=jnp.float32,
                            grid=grid,
                            knobs={"alg": "auto", "nb": None,
                                   "comm_precision": None,
                                   "redist_path": None})
    return kn["alg"]

bad = []
got = pick((8192, 512, 256), 2, 4)
if got != "slice":
    bad.append(f"tall-skinny 2x4: auto -> {got!r}, want 'slice'")
got = pick((8192, 512, 256), 1, 1)
if got != "dot":
    bad.append(f"1x1: auto -> {got!r}, want 'dot'")
if bad:
    print("TUNER-SELECTION PIN FAILURE:")
    for b in bad:
        print(f"  {b}")
    sys.exit(1)
print("tuner-selection pins ok (auto->slice 2x4 tall-skinny, auto->dot 1x1)")
PY
    echo "== slicing-gemm tier-1 tests (correctness + plans + knob) =="
    python -m pytest tests/blas/test_level3_slice.py \
        tests/core/test_slice_plan.py \
        tests/analysis/test_gemm_slice_plan.py \
        tests/tune/test_gemm_slice_knob.py \
        -q -m 'not slow' -p no:cacheprovider || rc=1
fi

if [ "$what" = "all" ] || [ "$what" = "kernels" ]; then
    echo "== pallas panel-kernel smoke (interpret mode, 1x1 + 2x2, CPU-safe) =="
    # clean pallas-panel runs of all three primitives through the real
    # drivers: residual-bounded factors, LU pivots bit-identical to xla
    JAX_PLATFORMS=cpu python -m perf.kernels smoke || rc=1
    echo "== comm-plan invariance under panel_impl='pallas' =="
    # panels are replicated-local compute: re-tracing every factorization
    # variant with the fused kernels selected must yield BYTE-identical
    # plan documents (and still pass the golden gate)
    python - <<'PY' || rc=1
import json, os, sys
sys.path.insert(0, os.getcwd())
from perf.comm_audit import GRIDS, _bootstrap, _grid, golden_path
_bootstrap()
from elemental_tpu import analysis as an
from elemental_tpu.analysis import diff_docs, golden_doc
from elemental_tpu.analysis.drivers import panel_impl_override
fams = [d for d in an.driver_names()
        if d.split("_")[0] in ("lu", "cholesky", "qr")
        and not d.startswith("qr_lq")]
bad = []
for d in fams:
    for grid in GRIDS:
        base, _, _ = an.trace_driver(d, _grid(*grid))
        base_doc = json.dumps(golden_doc(base), indent=1)
        with panel_impl_override("pallas"):
            plan, _, _ = an.trace_driver(d, _grid(*grid))
        doc = golden_doc(plan)
        if json.dumps(doc, indent=1) != base_doc:
            bad.append(f"{d} {grid[0]}x{grid[1]}: plan bytes changed")
        with open(golden_path(d, grid)) as f:
            if diff_docs(json.load(f), doc):
                bad.append(f"{d} {grid[0]}x{grid[1]}: golden diff")
if bad:
    print("COMM-PLAN INVARIANCE FAILURE under panel_impl='pallas':")
    for b in bad:
        print(f"  {b}")
    sys.exit(1)
print(f"comm-plan invariance ok ({len(fams)} variants x {len(GRIDS)} grids)")
PY
    echo "== kernels tests, full ladder incl. slow rungs =="
    python -m pytest tests/kernels -q -p no:cacheprovider || rc=1
fi

if [ "$what" = "all" ] || [ "$what" = "static" ]; then
    # the one-stop static slice (ISSUE 18): no device execution anywhere.
    # `check.sh static` alone also re-runs style + comm lint so it is a
    # self-contained pre-commit entry point; under `all` those two already
    # ran above and only the memory-side checks are new work here.
    if [ "$what" = "static" ]; then
        echo "== style lint =="
        if command -v ruff >/dev/null 2>&1; then
            ruff check . || rc=1
        else
            python tools/pyflakes_lite.py || rc=1
        fi
        echo "== comm-plan lint =="
        python -m perf.comm_audit lint --all || rc=1
    fi
    echo "== memory-plan lint (EL006-EL009) =="
    python -m perf.comm_audit mem-lint --all || rc=1
    echo "== golden memory-plan diff =="
    python -m perf.comm_audit mem-diff --all || rc=1
    echo "== golden coverage (comm + memory families) =="
    python tools/golden_coverage.py || rc=1
fi

if [ "$what" = "all" ] || [ "$what" = "serve" ]; then
    echo "== solver-service smoke (1x1 + 2x2, exec-cache reuse, CPU-safe) =="
    JAX_PLATFORMS=cpu python -m perf.serve smoke || rc=1
    echo "== chaos acceptance matrix (faults x targets x modes, 2x2) =="
    JAX_PLATFORMS=cpu python -m perf.serve chaos || rc=1
    echo "== serve tier-1 tests (admission/executor/policy/service/chaos) =="
    python -m pytest tests/serve -q -m 'not slow' -p no:cacheprovider || rc=1
fi

if [ "$what" = "all" ] || [ "$what" = "fleet" ]; then
    echo "== solver-fleet smoke (multi-grid routing, quota, chaos cells) =="
    JAX_PLATFORMS=cpu python -m perf.serve fleet-smoke || rc=1
    echo "== fleet tier-1 tests (scheduler/routing/fairness/chaos) =="
    python -m pytest tests/serve/test_fleet.py \
        tests/serve/test_fleet_fairness.py \
        tests/serve/test_fleet_chaos.py \
        -q -m 'not slow' -p no:cacheprovider || rc=1
fi

if [ "$rc" -eq 0 ]; then
    echo "check.sh: all gates passed"
else
    echo "check.sh: FAILURES (see above)" >&2
fi
exit "$rc"
