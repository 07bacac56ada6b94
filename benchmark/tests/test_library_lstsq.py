"""Kind ``library_lstsq`` (tall-skinny least squares) on four virtual CPU
devices at 8192 x 64: through the harness from a throw-away copy, its
answer against float64 numpy, a float32 normal-equations answer (on the
reference's collinear operand: graded columns alone do not trouble a
Cholesky factorization) and two other answers broken where they are
produced coming out as not correct, a program without the tall route
refused before it is compiled, and the three readers of the ``Least
squares`` layer on a hand-made trace and on the kinds' own facts."""
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_copy
import reference
import reference_lstsq
import run as harness
import scopes
import xplane
from test_scopes import entry_events

M, N, NRHS = 8192, 64, 8
#: the route reads 2.0e-8 to 2.3e-8 on the cell's kind of operand here
LIMIT = 2e-7
CONFIG = {"kind": "library_lstsq", "operator": "least_squares",
          "operand": "graded", "rows": M, "cols": N,
          "dtype": "float32", "grid": [2, 2],
          "limits": {"residual_angle": {"limit": LIMIT}},
          "printed_only": {"residual_norm": 1.0}}
#: the reference's collinear operand, for the normal equations.  XLA's CPU
#: reductions accumulate in sequence: the route reads up to 3e-4 on it
#: here, where a chip's tree reductions read 1e-5
COLLINEAR = {**CONFIG, "operand": "graded_collinear",
             "limits": {"residual_angle": {"limit": 1e-3}}}
CELL = {"config": "t-lstsq-2x2", "traffic": "b2b.rhs8", "chips": 4,
        "why": "test"}
SEED = 2147483999


@pytest.fixture
def bench_dir(tmp_path, monkeypatch):
    """A throw-away copy with the test's cell; the check in blocks of 2048
    rows (four of them), and the program's rule reaching the route at the
    test's size (it ships with the aspect measured on the chip, 8192 rows
    a column a chip)."""
    monkeypatch.setattr(reference_lstsq, "BLOCK_ROWS", 2048)
    monkeypatch.setattr(importlib.import_module("elemental_tpu.lapack.qr"),
                        "_TALL_ASPECT", 4)
    dst = bench_copy.make(tmp_path / "benchmark")
    bench_copy.write_json(os.path.join(dst, "configs", "t-lstsq-2x2.json"),
                          CONFIG)
    bench_copy.write_json(os.path.join(dst, "workloads", "t.lstsq.2x2.json"),
                          CELL)
    return dst


def run_cell(bench_dir, cell):
    return harness.main(["--workload", cell, "--seed", str(SEED),
                         "--seconds", "0.2", "--trace", "0"],
                        bench_dir=bench_dir, devices=jax.devices()[:4])


def operands(seed, i, m=M, n=N, nrhs=NRHS,
             entry=reference_lstsq.entry_graded):
    ka = np.uint32(reference.operand_key(seed, i, 0))
    kb = np.uint32(reference.operand_key(seed, i, 1))
    entry_a = entry(n, ka)
    entry_b = reference.entry_uniform_pm1(nrhs, kb)
    A = np.asarray(reference.plain_block(entry_a, 0, m, n), np.float64)
    B = np.asarray(reference.plain_block(entry_b, 0, m, nrhs), np.float64)
    return entry_a, entry_b, A, B


def angle(A, B, X):
    R = B - A @ X
    return np.max(np.abs(A.T @ R) / (np.linalg.norm(A, axis=0)[:, None]
                                     * np.linalg.norm(R, axis=0)[None, :]))


def test_run_is_correct_and_reports_every_end_to_end_metric(bench_dir):
    line = run_cell(bench_dir, "t.lstsq.2x2")
    assert line["correct"] and line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["metrics"]) == {"solve_s", "plan_gb", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())


def test_answer_matches_float64_numpy(bench_dir):
    """X of the timed path against ``numpy.linalg.lstsq`` in float64 on
    the same generated A and B, by the check's own number recomputed in
    float64 numpy; the flop and byte counts."""
    import elemental_tpu as el
    _cell, config, traffic = harness.resolve(bench_dir, "t.lstsq.2x2")
    kind = harness.load_module(bench_dir, "kinds", config["kind"])
    session = kind.setup(config, traffic, jax.devices()[:4], 7)
    X = session.solve(session.prepare(3))
    got = session.check(3, X)
    Xg = np.asarray(el.to_global(X), np.float64)
    _ea, _eb, A, B = operands(7, 3)
    want, *_ = np.linalg.lstsq(A, B, rcond=None)
    assert angle(A, B, want) < 1e-12
    assert got["residual_angle"] == pytest.approx(angle(A, B, Xg), rel=0.2)
    assert got["residual_angle"] < LIMIT
    R = B - A @ Xg
    assert got["residual_norm"] == pytest.approx(
        np.max(np.linalg.norm(R, axis=0) / np.linalg.norm(B, axis=0)),
        rel=1e-3)
    facts = session.facts
    assert facts["flops_per_solve"] == (
        2 * M * N * N - 2 * N ** 3 / 3 + 4 * M * N * NRHS
        - 2 * N * N * NRHS + N * N * NRHS)
    assert facts["lstsq_bytes"] == 4 * M * (N + NRHS)
    assert "n" not in facts and "nb" not in facts
    assert facts["collectives"]["all-to-all"] == 2
    assert facts["collectives"]["all-gather"] == 2


def test_residuals_lstsq_reads_a_float64_answer_as_rounding():
    """numpy's float64 minimizer, rounded to float32, reads at the check's
    own float32 rounding; each way of being wrong reads over it by
    orders; blocks that do not divide the rows are masked."""
    entry_a, entry_b, A, B = operands(5, 0)
    want = np.linalg.lstsq(A, B, rcond=None)[0].astype(np.float32)

    def numbers(X, block_rows=2048):
        return {k: float(v) for k, v in reference_lstsq.residuals_lstsq(
            entry_a, entry_b, M, N, NRHS, jnp.asarray(X), None,
            block_rows).items()}
    good = numbers(want)
    assert good["residual_angle"] < LIMIT / 3
    # B is noise: the fit explains n / m of it
    assert good["residual_norm"] == pytest.approx(
        np.sqrt(1 - N / M), abs=2e-3)
    assert numbers(want, 3000)["residual_angle"] == pytest.approx(
        good["residual_angle"], rel=0.5)
    # noise is orthogonal to a column within a few 1 / sqrt(m) = 0.011
    assert numbers(np.zeros_like(want))["residual_angle"] > 1e-2
    assert numbers(np.zeros_like(want))["residual_norm"] == pytest.approx(1.0)
    assert numbers(1.01 * want)["residual_angle"] > 100 * LIMIT
    swapped = want.copy()
    swapped[[0, 1]] = swapped[[1, 0]]
    assert numbers(swapped)["residual_angle"] > 1000 * LIMIT


def test_float32_normal_equations_fail_where_float64_ones_pass():
    """On the reference's COLLINEAR operand the float32 Gram matrix is not
    positive definite (numpy's Cholesky raises; XLA's returns NaN).  On
    the cell's operand, whose columns are only graded, the float32 normal
    equations read what Householder QR reads: a Cholesky factorization is
    as accurate on D G D as on G."""
    _ea, _eb, A, B = operands(5, 0, 16384, 256)
    A32 = A.astype(np.float32)
    L = np.linalg.cholesky(A32.T @ A32)
    X = np.linalg.solve(L.T, np.linalg.solve(L, A32.T @ B.astype(np.float32)))
    assert angle(A, B, X.astype(np.float64)) < 1e-7
    _ea, _eb, A, B = operands(
        5, 0, 16384, 256, entry=reference_lstsq.entry_graded_collinear)
    G32 = (A.astype(np.float32).T @ A.astype(np.float32))
    with pytest.raises(np.linalg.LinAlgError):
        np.linalg.cholesky(G32)
    L = np.linalg.cholesky(A.T @ A)
    X = np.linalg.solve(L.T, np.linalg.solve(L, A.T @ B))
    assert angle(A, B, X) < 1e-6


BROKEN = {
    # float32 normal equations: the Gram matrix and its Cholesky factor
    "normal_equations": (
        "    def altered(operands):\n"
        "        A, B = (el.to_global(x) for x in operands)\n"
        "        hi = jax.lax.Precision.HIGHEST\n"
        "        L = jnp.linalg.cholesky(jnp.matmul(A.T, A, precision=hi))\n"
        "        Y = jax.scipy.linalg.solve_triangular(\n"
        "            L, jnp.matmul(A.T, B, precision=hi), lower=True)\n"
        "        X = jax.scipy.linalg.solve_triangular(L.T, Y, lower=False)\n"
        "        return el.from_global(X, el.MC, el.MR, grid=operands[0].grid)\n"),
    "scale": (
        "    def altered(operands):\n"
        "        X = solve(operands)\n"
        "        return X.with_local(X.local * 1.01)\n"),
    "swap": (
        "    def altered(operands):\n"
        "        X = solve(operands)\n"
        "        return X.with_local(X.local[::-1])\n"),
}


@pytest.mark.parametrize("how", sorted(BROKEN))
def test_broken_timed_path_is_not_correct(bench_dir, how):
    """A kind, added as a new file, whose solve is another method (float32
    normal equations) or alters the answer where it is produced (X scaled
    by 1.01; its local rows reversed): ``correct`` is false and every
    solve counts as failed."""
    with open(os.path.join(bench_dir, "kinds", "broken_lstsq.py"), "w") as f:
        f.write(
            "import jax\nimport jax.numpy as jnp\n"
            "import elemental_tpu as el\nimport run as harness\n"
            "def setup(config, traffic, devices, seed):\n"
            "    good = harness.load_module(%r, 'kinds', 'library_lstsq')\n"
            "    session = good.setup(config, traffic, devices, seed)\n"
            "    solve = session.solve\n"
            "%s"
            "    session.solve = altered\n"
            "    return session\n" % (bench_dir, BROKEN[how]))
    bench_copy.write_json(
        os.path.join(bench_dir, "configs", "t-broken-lstsq.json"),
        {**(COLLINEAR if how == "normal_equations" else CONFIG),
         "kind": "broken_lstsq"})
    bench_copy.write_json(
        os.path.join(bench_dir, "workloads", "t.broken.lstsq.json"),
        {**CELL, "config": "t-broken-lstsq"})
    line = run_cell(bench_dir, "t.broken.lstsq")
    assert line["correct"] is False
    assert line["failed"] == line["attempted"] >= 1


def test_a_program_without_the_tall_route_is_refused(bench_dir, monkeypatch):
    """The parent of the PR that added the route: ``least_squares`` ticks
    no ``lstsq_route{kind=tall}``, and the kind exits before the solve is
    compiled (its blocked route gathers the whole operand to every
    chip)."""
    _cell, config, traffic = harness.resolve(bench_dir, "t.lstsq.2x2")
    kind = harness.load_module(bench_dir, "kinds", config["kind"])
    compiled = []

    def parents(A, B, **kw):                # ticks nothing, as the parent
        compiled.append(kw)
        return B
    monkeypatch.setitem(kind.OPERATORS, "least_squares", parents)
    with pytest.raises(SystemExit, match="no tall-skinny route"):
        kind.setup(config, traffic, jax.devices()[:4], SEED)
    assert compiled == [{}]                 # traced once, nb not passed


# --------------------------------------------- the Least squares readers

P = "jit(bench_solve)/jit(main)/el.least_squares/el.tsqr/"
S = P + "shard_map/"

#: the route in miniature: the two hops, the chip's own QR (a while loop's
#: body and a product), Q^T B, the tree's gather and its QR, the solve,
#: the write-back, a constant at the shard_map's edge, a compiler's copy
HLO = f"""HloModule jit_bench_solve, is_scheduled=true

ENTRY %main.9 (A: f32[8,8]) -> f32[8,8] {{
  %A = f32[8,8]{{1,0}} parameter(0), metadata={{op_name="A.local"}}
  %all-to-all.1 = f32[8,8]{{1,0}} all-to-all(%A), metadata={{op_name="{P}el.redist.MC_MR.to.VC_STAR/jit(_redistribute_jit)/shard_map/all_to_all"}}
  %copy.1 = f32[8,8]{{1,0}} copy(%A), metadata={{op_name="{P}el.redist.MC_MR.to.VC_STAR/jit(_redistribute_jit)/shard_map/reshape"}}
  %fusion.1 = f32[8,8]{{1,0}} fusion(%A), kind=kLoop, calls=%f, metadata={{op_name="{S}k00/local/while/body/mul"}}
  %dot.1 = f32[8,8]{{1,0}} dot(%A, %A), metadata={{op_name="{S}k00/local/dot_general"}}
  %dot.2 = f32[8,8]{{1,0}} dot(%A, %A), metadata={{op_name="{S}k00/applyq/dot_general"}}
  %all-gather.1 = f32[8,8]{{1,0}} all-gather(%A), metadata={{op_name="{S}k00/tree/all_gather"}}
  %fusion.2 = f32[8,8]{{1,0}} fusion(%A), kind=kLoop, calls=%f, metadata={{op_name="{S}k00/tree/while/body/mul"}}
  %custom-call.1 = f32[8,8]{{1,0}} custom-call(%A), metadata={{op_name="{S}k00/solve/triangular_solve"}}
  %fusion.3 = f32[8,8]{{1,0}} fusion(%A), kind=kLoop, calls=%f, metadata={{op_name="{P}el.redist.STAR_STAR.to.MC_MR/jit(_redistribute_jit)/shard_map/dynamic_slice"}}
  %broadcast.1 = f32[8,8]{{1,0}} broadcast(%A), metadata={{op_name="{S}broadcast.26"}}
  ROOT %copy.7 = f32[8,8]{{1,0}} copy(%fusion.3)
}}
"""

#: instruction -> ns; 400 ns busy a solve
DURATIONS = {"all-to-all.1": 60, "copy.1": 40, "fusion.1": 150, "dot.1": 50,
             "dot.2": 20, "all-gather.1": 8, "fusion.2": 32,
             "custom-call.1": 10, "fusion.3": 6, "broadcast.1": 4,
             "copy.7": 20}
READERS = ("tsqr_local_share", "tsqr_tree_share", "lstsq_hbm_util")


def hand_made_trace(solves=2):
    ops, modules, t = [], [], 1000.0
    for _ in range(solves):
        start = t
        for name, dur in DURATIONS.items():
            ops.append((f"{name} f32[8,8]", t, float(dur)))
            t += dur
        modules.append(("jit_bench_solve(1)", start, t - start))
        t += 500.0
    return xplane.reduce_trace(
        {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": modules}},
        "jit_bench_solve")


def run_of(operator="least_squares", **more):
    return {"facts": {"operator": operator, "chips": 1, "rows": 4096,
                      "cols": 8, "nrhs": 2,
                      "solve_module": "jit_bench_solve",
                      "lstsq_bytes": 16000.0, **more},
            "peak": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 100e9}}


def readers(names=READERS):
    return {name: harness.load_module(bench_copy.BENCH, "layer_metrics",
                                      name) for name in names}


def test_least_squares_readers_on_a_hand_made_trace(monkeypatch, capsys):
    monkeypatch.setattr(scopes, "module_texts", lambda name: [HLO])
    trace, run = hand_made_trace(), run_of()
    got = {name: r.read(trace, run) for name, r in readers().items()}
    assert got["tsqr_local_share"] == pytest.approx(100 * 200 / 400)
    assert got["tsqr_tree_share"] == pytest.approx(100 * 40 / 400)
    # 16000 bytes in 400 ns are 40 GB/s of the 100 GB/s peak
    assert got["lstsq_hbm_util"] == pytest.approx(40.0)
    summary = scopes.summary(trace, run)
    assert summary["sum"] == pytest.approx(100.0)
    assert summary["seconds"]["tsqr/applyq"] == pytest.approx(20e-9)
    assert summary["seconds"]["tsqr/solve"] == pytest.approx(10e-9)
    assert summary["seconds"]["tsqr/-"] == pytest.approx(4e-9)
    assert summary["share"]["redist"] == pytest.approx(100 * 106 / 400)
    assert summary["share"]["unscoped"] == pytest.approx(5.0)
    assert "tsqr/local" in capsys.readouterr().out


def test_least_squares_readers_are_silent_elsewhere(monkeypatch):
    """Another operator; a program that names nothing; a program whose
    least_squares names other scopes (the parent's blocked route); a peak
    table without the bandwidth; facts without the bytes."""
    monkeypatch.setattr(scopes, "module_texts", lambda name: [HLO])
    trace = hand_made_trace()
    for operator in ("hpd_solve", "lu_solve", "herm_eig"):
        assert all(r.read(trace, run_of(operator)) is None
                   for r in readers().values()), operator
    no_peak = {**run_of(), "peak": {"bf16_flops_per_s": 1e12}}
    assert readers()["lstsq_hbm_util"].read(trace, no_peak) is None
    no_bytes = run_of()
    del no_bytes["facts"]["lstsq_bytes"]
    assert readers()["lstsq_hbm_util"].read(trace, no_bytes) is None
    bare = "\n".join(line.split(", metadata=")[0] for line in
                     HLO.split("\n"))
    monkeypatch.setattr(scopes, "module_texts", lambda name: [bare])
    trace = hand_made_trace()                       # a fresh cache entry
    got = {name: r.read(trace, run_of()) for name, r in readers().items()}
    assert got["tsqr_local_share"] is None and got["tsqr_tree_share"] is None
    blocked = HLO.replace("el.tsqr/shard_map/k00/local", "el.qr/k00/panel") \
        .replace("el.tsqr/shard_map/k00/tree", "el.qr/k00/update")
    monkeypatch.setattr(scopes, "module_texts", lambda name: [blocked])
    trace = hand_made_trace()
    got = {name: r.read(trace, run_of()) for name, r in readers().items()}
    assert got["tsqr_local_share"] is None and got["tsqr_tree_share"] is None


def test_other_kinds_readers_are_silent_on_this_kinds_facts(monkeypatch):
    """``plan_shards`` divides by a SQUARE operand's shard and reads
    nothing where the facts state ``rows`` and ``cols`` and no ``n``; the
    eigensolve's and the grid LU's readers read nothing either."""
    monkeypatch.setattr(scopes, "module_texts", lambda name: [HLO])
    trace = hand_made_trace()
    run = run_of(chips=4, plan_bytes=6.6e9, hlo_lines=100)
    others = readers(("plan_shards", "hemv_share", "dc_share",
                      "backtransform_share", "hemv_hbm_util", "swap_share",
                      "row_permute_share", "panel_gather_share"))
    got = {name: r.read(trace, run) for name, r in others.items()}
    assert got["plan_shards"] is None
    for name in ("hemv_share", "dc_share", "backtransform_share",
                 "hemv_hbm_util", "row_permute_share", "panel_gather_share"):
        assert got[name] is None, name


def test_every_reader_reads_the_kinds_facts(bench_dir):
    """Every file under ``layer_metrics/`` called on the facts of a real
    session, every op of the compiled program's entry given 10 ns: no
    reader asks the kind for a key it does not give."""
    _cell, config, traffic = harness.resolve(bench_dir, "t.lstsq.2x2")
    kind = harness.load_module(bench_dir, "kinds", config["kind"])
    harness.enable_cache()      # as main does: the set-up readers' log
    session = kind.setup(config, traffic, jax.devices()[:4], SEED)
    assert harness.judge([session.warm], config["limits"]) == 0
    name = session.facts["solve_module"]
    assert name == "jit_bench_solve"
    ops = entry_events(session._solve.as_text())
    window = [(f"{name}(1)", 1000.0, 10.0 * len(ops))]
    trace = xplane.reduce_trace(
        {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": window}}, name)
    run = {"facts": session.facts, "setup_s": 1.0,
           "peak": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}
    metrics = harness.read_metrics(bench_dir, "layer_metrics", trace, run)
    for reader in ("device_idle_share", "flops_util", "hlo_lines",
                   "panel_share", "update_share", "sweep_share",
                   "unscoped_share", "redist_share", "collective_op_share",
                   "tsqr_local_share", "tsqr_tree_share", "lstsq_hbm_util"):
        assert reader in metrics, reader
    assert metrics["tsqr_local_share"]["value"] > 0.0
    assert metrics["tsqr_tree_share"]["value"] > 0.0
    assert metrics["redist_share"]["value"] > 0.0
    for reader in ("plan_shards", "hemv_share", "dc_share",
                   "backtransform_share", "hemv_hbm_util",
                   "row_permute_share", "panel_gather_share"):
        assert reader not in metrics, reader
    # the driver's rule for the result's line: the cell owes every
    # per-layer metric that lists it, and every one that lists no cells
    with open(os.path.join(os.path.dirname(bench_copy.BENCH),
                           "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    cell = "lstsq.2x2.b2b"
    owed = {m["name"] for m in per_layer if cell in m.get("workloads", [cell])}
    assert owed <= set(metrics), sorted(owed - set(metrics))
