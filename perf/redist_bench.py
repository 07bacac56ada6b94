"""Redistribution microbench: src->dst x geometry x path matrix (ISSUE 12).

Times the SAME redistribution through the chained multi-hop engine
(``path='chain'``) and the one-shot compiled plan (``path='direct'``) on
the live device grid, bracketed by a matmul roofline so chip weather is
factored out of an A/B pair.  Each row prints as one
``redist_bench/v1`` JSON line:

    {"schema": "redist_bench/v1", "pair": "[MC,MR]->[MR,STAR]",
     "grid": "2x4", "n": 4096, "path": "direct", "plan": "a2a",
     "rounds": 1, "model_bytes": ..., "seconds": ..., "gbps": ...,
     "roof_tflops": [r_before, r_after], "match": true}

``model_bytes`` is the ring-model per-device wire estimate (the same
alpha-beta terms the tuner's cost model and the ``'auto'`` path arbiter
price: chain legs at all_gather/all_to_all/ppermute ring cost, the direct
plan at its single-collective slot volume), so ``gbps`` is MODEL bytes
over measured seconds -- comparable across paths, not a NIC counter.
``match`` cross-checks the two paths bit-identically via ``to_global``
before timing (the bench never reports a speedup for a wrong answer).

Usage:

    python -m perf.redist_bench                   # default pair matrix on
                                                  #   the full device grid
    python -m perf.redist_bench --smoke           # 1x1 grid, n=64, two
                                                  #   pairs, tiny roofline
    python -m perf.redist_bench --n 4096 --grid 2x4 --paths chain,direct
    python -m perf.redist_bench --pairs "MC,MR->MR,STAR;VC,STAR->VR,STAR"
    python -m perf.redist_bench --record   # also least-squares-fit alpha
                                           #   (s/round) + bandwidth from the
                                           #   measured rows and save them as
                                           #   redist_constants/v1 in the
                                           #   tuning cache; the engine's
                                           #   'auto' arbitration consults
                                           #   them before the ring model

    python -m perf.redist_bench --unpack          # the LOCAL unpack that
                                                  #   follows a gather, alone,
                                                  #   on one device (below)
    python -m perf.redist_bench --unpack --blocks "2x2x1024x1024;4x7680x2048:0"
    python -m perf.redist_bench --filter          # the LOCAL cyclic slice
                                                  #   that makes a replicated
                                                  #   dimension distributed,
                                                  #   alone and behind an
                                                  #   interleave (below)

``--unpack`` (ISSUE 29) times ``redist.engine``'s interleave on ONE device,
on blocks synthesized from a seed, against an elementwise pass over the
same bytes (one read and one write of the block, what a copy costs), so
the per-unpack figure of the 2x2 benchmark cell can be read again without
the four-chip machine.  A block is ``RxCxLRxLC`` (the ``r*c`` blocks of an
[MC,MR] matrix, unpacked in both dimensions as ``[MC,MR] -> [STAR,STAR]``
does; also timed through ``one_transpose``, the single 4-D transpose the
engine used before, whose intermediate the TPU pads 64-fold) or
``SxLRxLC:dim`` (``S`` blocks interleaved along ``dim``).  The default
blocks are the cell's: the diagonal block, the crossover tail, and the
step-0 panels of ``panel_spread``, ``[MC,MR]->[STAR,MC]`` and
``[MC,MR]->[VC,STAR]``.  One ``redist_unpack_bench/v1`` line per form:

    {"schema": "redist_unpack_bench/v1", "block": "2x2x1024x1024",
     "form": "engine", "dtype": "float32", "block_mb": 16.777216,
     "instances": [2, 4], "ms": ..., "x_copy": ..., "device": "TPU v5 lite"}

``ms`` is one unpack's time: two compiled programs unpack ``instances``
independent blocks each, and the difference of their least times over
``--reps`` calls, over the difference in blocks, leaves the dispatch and
the wait out.  ``x_copy`` is that over the ``copy`` form's.  Every form
is checked equal, bit for bit, to ``engine`` before it is timed.

``--filter`` (ISSUE 32) is the mirror: ``redist.engine``'s de-interleave
of a float32 block ``SxLRxLC:dim`` (the ``LR x LC`` block, of which the
slice ``i = iLoc*S + shift`` of dimension ``dim`` is kept; ``shift`` is an
argument of the timed program, as it is traced in the engine), or the
composed ``SxLRxLC:1>0``: ``S`` blocks interleaved along the lanes, the
result returned AND row-filtered, ONE jitted function, as the 2x2 LU
cell's row block runs ``[STAR,VR] -> [STAR,MR] -> [MC,MR]``; alone each
half compiles clean and hides what the pair costs.  The default blocks
are that cell's.  Forms: ``copy`` (a plain copy of the input block),
``elementwise`` (one pass over it), ``engine`` (reshape to
``(..., l, S, ...)`` and index, behind ``optimization_barrier``), and the
forms the engine does not use, kept to be measured again:
``reshape_index`` (the same with no barrier: the engine before ISSUE 32
on rows), ``strided`` (``S`` static strided slices and a select on
``shift``),
``switch`` (the same slices as the branches of a ``lax.switch``) and, on
rows, ``lane_slice`` (reshape to ``(l, S*LC)`` and a lane-aligned
dynamic slice).  One ``redist_filter_bench/v1`` line per form, the fields
of ``redist_unpack_bench/v1``.

On a CPU-only host run under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (set automatically
when unset) so the multi-chip grids exist; timings there are functional,
not representative -- the bench is for TPU pods, the smoke mode for CI.
"""
import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: default src->dst matrix: one representative of each plan regime --
#: the 3-hop gather chains gemm feeds on, a pure relabeling (ppermute),
#: a replication (fused all_gather chain vs one-shot a2a+concat), and a
#: transpose-style move.
DEFAULT_PAIRS = (
    ("MC,MR", "MR,STAR"),
    ("MC,MR", "STAR,VC"),
    ("MC,MR", "STAR,STAR"),
    ("VC,STAR", "VR,STAR"),
    ("MC,MR", "MR,MC"),
    ("VC,STAR", "MC,STAR"),
)

SMOKE_PAIRS = DEFAULT_PAIRS[:2]


def _bootstrap():
    """Make multi-device grids exist on CPU-only hosts (virtual devices
    must be requested BEFORE jax initializes); never downgrades a TPU."""
    if _REPO not in sys.path:
        sys.path.insert(0, _REPO)
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()


def _min_t(fn, reps):
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return min(ts)


def _dist_pair(spec: str):
    import elemental_tpu as el
    by_name = {d.value: d for d in
               (el.MC, el.MR, el.VC, el.VR, el.STAR, el.MD, el.CIRC)}
    try:
        c, r = (by_name[s.strip().upper()] for s in spec.split(","))
    except (KeyError, ValueError):
        raise SystemExit(f"bad dist pair {spec!r}; want e.g. 'MC,MR'")
    return (c, r)


def _parse_pairs(arg: str):
    out = []
    for leg in arg.split(";"):
        src, _, dst = leg.partition("->")
        if not dst:
            raise SystemExit(f"bad pair {leg!r}; want 'MC,MR->MR,STAR'")
        out.append((src.strip(), dst.strip()))
    return tuple(out)


def _label(pair) -> str:
    return f"[{pair[0].value},{pair[1].value}]"


def _roofline(n: int) -> float:
    """Matmul roofline at size n (chip-weather bracket)."""
    import jax
    import jax.numpy as jnp
    HI = jax.lax.Precision.HIGHEST
    x = jax.random.normal(jax.random.PRNGKey(9), (n, n), jnp.float32)
    mm = jax.jit(lambda a: jnp.matmul(a, a, precision=HI))
    float(mm(x)[0, 0])                       # compile, untimed
    dt = max(_min_t(lambda: float(mm(x)[0, 0]), 3), 1e-9)
    return 2 * n ** 3 / dt / 1e12


def _model_bytes(src, dst, gshape, grid_shape, itemsize, path):
    """Ring-model per-device wire estimate for one redistribution: the
    chain priced leg by leg, the direct path by its compiled plan."""
    from elemental_tpu.redist.engine import chain_cost
    from elemental_tpu.redist.plan import compile_plan
    if path == "direct":
        plan = compile_plan(src, dst, gshape, grid_shape)
        if plan is not None:
            return plan.rounds, plan.wire_bytes(itemsize), plan.kind
        path = "chain"                       # engine falls back identically
    rounds, nbytes = chain_cost(src, dst, gshape, grid_shape, itemsize)
    return rounds, nbytes, "chain"


def run_pair(grid, n, src, dst, paths, reps=3, check=True):
    """Time one src->dst move under each path; returns a list of row dicts
    (no JSON printing -- the CLI feeds from here)."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    import elemental_tpu as el

    host = np.asarray(
        np.arange(n * n, dtype=np.float32).reshape(n, n) % 1013 / 7.0)
    A = el.from_global(jnp.asarray(host), src[0], src[1], grid)
    grid_shape = (grid.height, grid.width)
    itemsize = jnp.dtype(A.dtype).itemsize

    match = None
    if check:
        outs = [np.asarray(el.to_global(
            el.redistribute(A, dst[0], dst[1], path=p))) for p in paths]
        match = all(np.array_equal(outs[0], o) for o in outs[1:]) \
            and np.array_equal(outs[0], host)

    rows = []
    for path in paths:
        out = el.redistribute(A, dst[0], dst[1], path=path)   # warm cache
        jax.block_until_ready(out.local)

        def _step(p=path):
            o = el.redistribute(A, dst[0], dst[1], path=p)
            float(jnp.ravel(o.local)[0])     # force completion

        dt = max(_min_t(_step, reps), 1e-9)
        rounds, nbytes, plan_kind = _model_bytes(
            src, dst, (n, n), grid_shape, itemsize, path)
        rows.append({
            "schema": "redist_bench/v1",
            "pair": f"{_label(src)}->{_label(dst)}",
            "grid": f"{grid.height}x{grid.width}",
            "n": n,
            "path": path,
            "plan": plan_kind,
            "rounds": rounds,
            "model_bytes": nbytes,
            "seconds": dt,
            "gbps": nbytes / dt / 1e9,
            "match": match,
        })
    return rows


def fit_constants(rows):
    """Least-squares fit ``seconds = alpha * rounds + model_bytes / bw``
    over measured rows; returns ``(alpha_s, bw_bytes_per_s, nsamples)`` or
    None when the system is degenerate (e.g. a 1x1 grid where every row
    has zero rounds and zero bytes -- nothing to fit)."""
    import numpy as np
    samples = [(row["rounds"], row["model_bytes"], row["seconds"])
               for row in rows if row["rounds"] > 0 and row["seconds"] > 0]
    if len(samples) < 2:
        return None
    M = np.array([[float(r_), float(b_)] for r_, b_, _ in samples])
    t = np.array([s_ for _, _, s_ in samples])
    if np.linalg.matrix_rank(M) < 2:
        return None
    coef, *_ = np.linalg.lstsq(M, t, rcond=None)
    alpha = float(max(coef[0], 1e-9))        # s per collective round
    beta = float(max(coef[1], 1e-15))        # s per wire byte
    return alpha, 1.0 / beta, len(samples)


def record_constants(grid_shape, rows):
    """Fit + persist ``redist_constants/v1`` for one grid; returns the doc
    (with ``_path``) or None when the fit is degenerate."""
    import jax
    from elemental_tpu.tune.cache import (load_redist_constants,
                                          save_redist_constants)
    fit = fit_constants(rows)
    if fit is None:
        return None
    alpha, bw, nsamples = fit
    backend = jax.default_backend()
    path = save_redist_constants(grid_shape, backend, alpha, bw,
                                 nsamples=nsamples)
    doc = dict(load_redist_constants(grid_shape, backend) or
               {"schema": "redist_constants/v1", "alpha_s": alpha,
                "bw_bytes_per_s": bw})
    doc["_path"] = path
    return doc


#: the 2x2 cell's unpacks (N = 32768, nb = 2048, f32): the diagonal block,
#: the crossover tail, then step 0's panel_spread gather (rows, S = 4), the
#: [VC,*] -> [MC,*] partial gather of [MC,MR]->[STAR,MC] (columns, S = 2)
#: and the all_to_all unpack of [MC,MR]->[VC,STAR] (columns, S = 2)
UNPACK_BLOCKS = ("2x2x1024x1024", "2x2x2048x2048", "4x7680x2048:0",
                 "2x2048x7680:1", "2x7680x1024:1")

#: bytes of input the smaller timed program holds, at most (it holds as much
#: again in results, and one_transpose's padded temporaries beside them)
_UNPACK_BYTES = 64 << 20


def _unpack_forms(spec: str):
    """``(block shape, {form: fn(blocks) -> unpacked})`` of one block spec."""
    import elemental_tpu as el
    from elemental_tpu.redist import engine
    dims, _, dim = spec.partition(":")
    try:
        shape = tuple(int(v) for v in dims.split("x"))
        if len(shape) == 4 and not dim:
            r, c, lr, lc = shape
            return shape, {
                "copy": lambda G: G + 1,
                "engine": lambda G: engine._interleave_2d(G, (el.MC, el.MR)),
                "one_transpose": lambda G: G.transpose(2, 0, 3, 1).reshape(
                    lr * r, lc * c)}
        if len(shape) == 3 and dim in ("0", "1"):
            return shape, {
                "copy": lambda g: g + 1,
                "engine": lambda g: engine._interleave(g, int(dim))}
    except ValueError:
        pass
    raise SystemExit(f"bad block {spec!r}; want 'RxCxLRxLC' or 'SxLRxLC:dim'")


def _time_forms(schema, spec, forms, x, extra, reps):
    """One row per form: two compiled programs run ``fn(x[i], *extra)`` on
    ``k`` and on all ``2k`` of the independent blocks ``x``, and the
    difference of their least times over the difference in blocks leaves
    the dispatch and the wait out.  Every form but ``copy`` and
    ``elementwise`` is checked equal, bit for bit, to ``engine`` (which
    comes before it)."""
    import numpy as np
    import jax
    k, nbytes = x.shape[0] // 2, x[0].nbytes
    want = None
    rows = []
    for form, fn in forms.items():
        secs = []
        for n in (k, 2 * k):
            f = jax.jit(lambda xs, *a, fn=fn, n=n: tuple(
                fn(xs[i], *a) for i in range(n)))
            out = jax.block_until_ready(f(x, *extra))   # compile, untimed
            if form == "engine":
                want = out
            elif form not in ("copy", "elementwise") and not all(
                    np.array_equal(np.asarray(a), np.asarray(b))
                    for a, b in zip(jax.tree.leaves(out),
                                    jax.tree.leaves(want))):
                raise SystemExit(f"{spec}: {form} differs from engine")
            del out
            secs.append(_min_t(
                lambda: jax.block_until_ready(f(x, *extra)), reps))
        rows.append({"schema": schema, "block": spec,
                     "form": form, "dtype": "float32",
                     "block_mb": nbytes / 1e6, "instances": [k, 2 * k],
                     "ms": max(secs[1] - secs[0], 1e-9) / k * 1e3,
                     "device": jax.devices()[0].device_kind})
    for row in rows:
        row["x_copy"] = row["ms"] / rows[0]["ms"]
    return rows


def _blocks_of(shape, seed):
    """``2k`` seeded float32 blocks of ``shape``, ``k`` (1 or 2) so that
    the smaller timed program holds at most ``_UNPACK_BYTES`` of them."""
    import math
    import jax
    import jax.numpy as jnp
    k = max(1, min(2, _UNPACK_BYTES // (math.prod(shape) * 4)))
    return jax.random.normal(
        jax.random.PRNGKey(seed), (2 * k,) + tuple(shape), jnp.float32)


def run_unpack(spec: str, reps: int = 7):
    """Time each form of one float32 block spec on the first device;
    returns the ``redist_unpack_bench/v1`` rows."""
    shape, forms = _unpack_forms(spec)
    return _time_forms("redist_unpack_bench/v1", spec, forms,
                       _blocks_of(shape, 29), (), reps)


#: the 2x2 LU cell's de-interleaves (N = 16384, nb = 2048, f32): the U row
#: block's write-back [STAR,MR] -> [MC,MR] at steps 0 and 1 (rows, S = 2),
#: the panel's write-back [STAR,STAR] -> [MC,MR] (rows, then lanes), the
#: Cholesky cell's [MC,STAR] -> [MC,MR] (lanes), and the row block's chain
#: [STAR,VR] -> [STAR,MR] -> [MC,MR] at steps 0 and 1, composed
FILTER_BLOCKS = ("2x2048x8192:0", "2x2048x7168:0", "2x16384x2048:0",
                 "2x8192x2048:1", "2x15360x2048:1", "2x2048x4096:1>0",
                 "2x2048x3584:1>0")


def _filter_forms(spec: str):
    """``(input shape, S, {form: fn(block, shift)})`` of one filter spec."""
    import jax.numpy as jnp
    from jax import lax
    from elemental_tpu.redist import engine
    dims, _, tail = spec.partition(":")
    try:
        S, lr, lc = (int(v) for v in dims.split("x"))
    except ValueError:
        S = 0
    if S < 1 or tail not in ("0", "1", "1>0"):
        raise SystemExit(f"bad block {spec!r}; want 'SxLRxLC:dim' or "
                         f"'SxLRxLC:1>0'")
    dim = 0 if tail == "1>0" else int(tail)

    def split(x):
        shape = list(x.shape)
        shape[dim:dim + 1] = [shape[dim] // S, S]
        return x.reshape(shape)

    def index(x, shift):
        return lax.dynamic_index_in_dim(split(x), shift, axis=dim + 1,
                                        keepdims=False)

    def strided(x, shift):
        return lax.select_n(shift, *(
            lax.slice_in_dim(x, s, x.shape[dim], stride=S, axis=dim)
            for s in range(S)))

    def lane_slice(x, shift):
        y = x.reshape(x.shape[0] // S, S * x.shape[1])
        return lax.dynamic_slice_in_dim(y, shift * x.shape[1], x.shape[1],
                                        axis=1)

    forms = {
        "engine": lambda x, shift: engine._deinterleave(x, dim, S, shift),
        "reshape_index": index,
        "strided": strided,
        "switch": lambda x, shift: lax.switch(shift, [
            lambda x, s=s: lax.slice_in_dim(x, s, x.shape[dim], stride=S,
                                            axis=dim)
            for s in range(S)], x)}
    if dim == 0:
        forms["lane_slice"] = lane_slice
    if tail == "1>0":
        # g holds the S gathered blocks; both results are returned, as the
        # LU step returns the [STAR,MR] block beside its [MC,MR] write-back
        def behind_interleave(fn):
            def composed(g, shift):
                full = engine._interleave(g, 1)
                return fn(full, shift), full
            return composed
        forms = {form: behind_interleave(fn) for form, fn in forms.items()}
        shape = (S, lr, lc)
    else:
        shape = (lr, lc)
    return shape, S, {"copy": lambda x, shift: jnp.copy(x),
                      "elementwise": lambda x, shift: x + 1, **forms}


def run_filter(spec: str, reps: int = 7):
    """Time each form of one filter spec on the first device; returns the
    ``redist_filter_bench/v1`` rows."""
    import jax.numpy as jnp
    shape, S, forms = _filter_forms(spec)
    return _time_forms("redist_filter_bench/v1", spec, forms,
                       _blocks_of(shape, 32), (jnp.int32(S - 1),), reps)


def main_local(argv, mode, blocks, run) -> int:
    """``--unpack`` / ``--filter``: one device, one line per form."""
    reps = 7
    it = iter(argv)
    for arg in it:
        if arg == mode:
            continue
        elif arg == "--blocks":
            blocks = tuple(b.strip() for b in next(it).split(";"))
        elif arg == "--reps":
            reps = int(next(it))
        else:
            raise SystemExit(f"unknown flag {arg!r} with {mode}")
    for spec in blocks:
        for row in run(spec, reps=reps):
            print(json.dumps(row), flush=True)
    return 0


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] in ("-h", "--help"):
        print(__doc__)
        return 0
    _bootstrap()
    if "--unpack" in argv:
        return main_local(argv, "--unpack", UNPACK_BLOCKS, run_unpack)
    if "--filter" in argv:
        return main_local(argv, "--filter", FILTER_BLOCKS, run_filter)
    import jax
    import elemental_tpu as el

    smoke = "--smoke" in argv
    record = "--record" in argv
    n = 64 if smoke else None
    grids = None
    paths = ("chain", "direct")
    pairs = SMOKE_PAIRS if smoke else DEFAULT_PAIRS
    reps = 3
    it = iter(argv)
    for arg in it:
        if arg in ("--smoke", "--record"):
            continue
        elif arg == "--n":
            n = int(next(it))
        elif arg == "--grid":
            r, c = next(it).split("x")
            grids = [(int(r), int(c))]
        elif arg == "--paths":
            paths = tuple(p.strip() for p in next(it).split(","))
        elif arg == "--pairs":
            pairs = _parse_pairs(next(it))
        elif arg == "--reps":
            reps = int(next(it))
        else:
            raise SystemExit(f"unknown flag {arg!r}")

    devs = jax.devices()
    if grids is None:
        if smoke:
            grids = [(1, 1)]
        else:
            # full device grid, plus a 1-row layout when it differs (the
            # same chips as a different geometry move different bytes)
            p = len(devs)
            r = 1
            for q in range(int(p ** 0.5), 0, -1):
                if p % q == 0:
                    r = q
                    break
            grids = [(r, p // r)] if r == 1 else [(r, p // r), (1, p)]
    if n is None:
        n = 256 if devs[0].platform == "cpu" else 4096

    roof_n = 256 if smoke or devs[0].platform == "cpu" else 8192
    for gr, gc in grids:
        if gr * gc > len(devs):
            print(f"# skip {gr}x{gc}: only {len(devs)} device(s)",
                  file=sys.stderr)
            continue
        grid = el.Grid(devs[: gr * gc], height=gr)
        r0 = _roofline(roof_n)
        rows = []
        for src_s, dst_s in pairs:
            src, dst = _dist_pair(src_s), _dist_pair(dst_s)
            rows += run_pair(grid, n, src, dst, paths, reps=reps)
        r1 = _roofline(roof_n)
        for row in rows:
            row["roof_tflops"] = [round(r0, 3), round(r1, 3)]
            print(json.dumps(row))
            if row["match"] is False:
                print(f"# MISMATCH {row['pair']} on {row['grid']}",
                      file=sys.stderr)
                return 1
        if record:
            doc = record_constants((gr, gc), rows)
            if doc is None:
                print(f"# record: degenerate fit on {gr}x{gc} "
                      f"(no multi-device rows), nothing written",
                      file=sys.stderr)
            else:
                print(json.dumps(doc))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
