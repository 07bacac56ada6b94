"""Traceable driver registry + the abstract trace entry point.

``trace_driver(name, grid, ...)`` builds storage-form abstract inputs for
a registered distributed driver, traces it with ``jax.make_jaxpr`` (no
device execution -- works under ``JAX_PLATFORMS=cpu``), and returns the
extracted :class:`~elemental_tpu.analysis.plan.CommPlan` together with
the closed jaxpr and the engine's redistribution log.

Registered drivers (ISSUE 3's golden set): ``gemm`` under every explicit
algorithm, ``trsm``, ``herk``, ``cholesky`` classic / look-ahead /
explicit-crossover, ``lu`` classic / look-ahead / explicit-crossover, and
``qr``.  Inputs default to float32 (n=64, nb=16) so the f64-promotion
lint (EL004) has teeth on the goldens.

Input construction note: inputs are built directly in stacked-storage
form (``DistMatrix(storage, ...)``) from ``ShapeDtypeStruct`` specs --
the ``from_global`` bridge would ``device_put`` eagerly and break the
pure-abstract trace.
"""
from __future__ import annotations

import contextlib
import dataclasses

import jax
import jax.numpy as jnp

from ..core import indexing as ix
from ..core.dist import Dist, storage_slots, stride as dist_stride
from ..core.distmatrix import DistMatrix
from ..core.grid import Grid
from ..redist.engine import redist_trace, redist_counts
from .jaxpr_walk import collect_events
from .plan import plan_from_parts

MC, MR = Dist.MC, Dist.MR

#: default trace geometry (4 blocked steps at 64/16; small enough that a
#: full registry sweep traces in seconds, large enough that look-ahead,
#: crossover, and the SUMMA panel loops all take their real schedules)
DEFAULT_N = 64
DEFAULT_NB = 16
#: explicit mid-range crossover for the *_crossover variants: at n=64 the
#: tail triggers after two distributed steps (64-32 <= 32), so the plan
#: shows pipelined steps AND the tail collapse in one snapshot
DEFAULT_XOVER = 32


def storage_shape(m: int, n: int, cdist: Dist, rdist: Dist, grid: Grid):
    """Stacked-storage array shape of a DistMatrix (outside shard_map)."""
    r, c = grid.height, grid.width
    lr = ix.max_local_length(m, dist_stride(cdist, r, c))
    lc = ix.max_local_length(n, dist_stride(rdist, r, c))
    return (storage_slots(cdist, r, c) * lr, storage_slots(rdist, r, c) * lc)


def _mcmr_input(grid, m, n, dtype):
    return jax.ShapeDtypeStruct(storage_shape(m, n, MC, MR, grid), dtype)


def _as_dm(a, grid, m, n):
    return DistMatrix(a, (m, n), MC, MR, 0, 0, grid)


@dataclasses.dataclass(frozen=True)
class DriverSpec:
    """One registry entry: builds the traced callable + abstract inputs."""
    name: str
    build: callable          # (grid, n, nb, dtype) -> (fn, args, meta)
    allow_bf16: bool = False
    #: lint EL006 budget: peak live bytes may not exceed this multiple of
    #: the driver's per-device input+output residency (see
    #: ``MEM_BUDGET_FACTORS`` for the declared exceptions)
    mem_budget_factor: float = 4.0


def _gemm_spec(alg, variant="", redist_path=None):
    def build(grid, n, nb, dtype):
        from ..blas.level3 import gemm

        def fn(a, b):
            A = _as_dm(a, grid, n, n)
            B = _as_dm(b, grid, n, n)
            return gemm(A, B, alg=alg, nb=nb, redist_path=redist_path)
        args = (_mcmr_input(grid, n, n, dtype), _mcmr_input(grid, n, n, dtype))
        meta = {"alg": alg}
        if redist_path is not None:
            meta["redist_path"] = redist_path
        return fn, args, meta
    name = f"gemm_{alg.lower()}"
    return DriverSpec(f"{name}_{variant}" if variant else name, build)


#: the slicing-gemm driver's rectangular trace geometry, as multiples of
#: the ``n`` trace parameter: (m, k, n) = (32n, n, n/4) -- the tall-skinny
#: class (m >> n, k = 4*cols) where ISSUE 16 pins the slice schedule at
#: strictly fewer collective rounds and >= 1.5x fewer wire bytes than the
#: stationary-C twin on BOTH golden grids (the twin ratio grows with m/n).
GEMM_SLICE_DIMS = (32, 1, 0.25)


def gemm_slice_extents(n: int) -> tuple:
    """(m, k, n') of the gemm_slice trace at trace parameter ``n``."""
    sm, sk, sn = GEMM_SLICE_DIMS
    return int(sm * n), int(sk * n), max(int(sn * n), 1)


def _gemm_slice_spec():
    """The slicing gemm (ISSUE 16) traces TALL-SKINNY, not square: its
    whole reason to exist is the rectangular regime, so the golden pins
    live where 'auto' would actually dispatch it."""
    def build(grid, n, nb, dtype):
        from ..blas.level3 import gemm
        m, k, n2 = gemm_slice_extents(n)

        def fn(a, b):
            A = _as_dm(a, grid, m, k)
            B = _as_dm(b, grid, k, n2)
            return gemm(A, B, alg="slice", nb=nb)
        args = (_mcmr_input(grid, m, k, dtype),
                _mcmr_input(grid, k, n2, dtype))
        meta = {"alg": "slice", "extents": [m, k, n2]}
        return fn, args, meta
    return DriverSpec("gemm_slice", build)


def _trsm_spec(variant="", side="L", redist_path=None):
    def build(grid, n, nb, dtype):
        from ..blas.level3 import trsm

        def fn(a, b):
            A = _as_dm(a, grid, n, n)
            B = _as_dm(b, grid, n, n)
            return trsm(side, "L", "N", A, B, nb=nb,
                        redist_path=redist_path)
        args = (_mcmr_input(grid, n, n, dtype), _mcmr_input(grid, n, n, dtype))
        meta = {}
        if side != "L":
            meta["side"] = side
        if redist_path is not None:
            meta["redist_path"] = redist_path
        return fn, args, meta
    return DriverSpec(f"trsm_{variant}" if variant else "trsm", build)


def _herk_spec(variant="", redist_path=None):
    def build(grid, n, nb, dtype):
        from ..blas.level3 import herk

        def fn(a):
            return herk("L", _as_dm(a, grid, n, n), nb=nb,
                        redist_path=redist_path)
        meta = {}
        if redist_path is not None:
            meta["redist_path"] = redist_path
        return fn, (_mcmr_input(grid, n, n, dtype),), meta
    return DriverSpec(f"herk_{variant}" if variant else "herk", build)


def _lq_spec(variant="", redist_path=None):
    def build(grid, n, nb, dtype):
        from ..lapack.qr import lq

        def fn(a):
            return lq(_as_dm(a, grid, n, n), nb=nb, redist_path=redist_path)
        meta = {}
        if redist_path is not None:
            meta["redist_path"] = redist_path
        return fn, (_mcmr_input(grid, n, n, dtype),), meta
    return DriverSpec(f"qr_lq_{variant}" if variant else "qr_lq", build)


def _redist_md_spec(variant="", redist_path=None):
    """[MC,MR] -> [MD,STAR] -> [STAR,MD] round-trip at RAGGED extents
    ((n-1, n-3): the diagonal locals straddle slot boundaries), the
    incompatible-residue pair whose one-shot plan exercises both ragged
    slot trimming and subgroup packing (ISSUE 13)."""
    def build(grid, n, nb, dtype):
        from ..core.dist import Dist
        from ..redist.engine import redistribute
        MD, STAR = Dist.MD, Dist.STAR
        m_, n_ = n - 1, n - 3

        def fn(a):
            A = _as_dm(a, grid, m_, n_)
            B = redistribute(A, MD, STAR, path=redist_path)
            return redistribute(B, STAR, MD, path=redist_path)
        meta = {"extents": [m_, n_]}
        if redist_path is not None:
            meta["redist_path"] = redist_path
        return fn, (_mcmr_input(grid, m_, n_, dtype),), meta
    return DriverSpec(f"redist_md_{variant}" if variant else "redist_md",
                      build)


def _redist_circ_spec(variant=""):
    """[MC,MR] -> [CIRC,CIRC] -> [VC,STAR]: both root-only endpoint
    legs (gather to root, scatter from root), landing on a THIRD pair
    so the lint does not read it as a redundant round trip.  Since
    ISSUE 14 both legs ride the jitted shard_map path (ONE fused gather
    chain to [STAR,STAR] + a root ``device_put`` out; a broadcast
    ``device_put`` + zero-collective local filter back), so the whole
    chain must trace WITHOUT an eager host sync -- this driver existing
    at all pins that (the former eager bridge could not be abstractly
    traced)."""
    def build(grid, n, nb, dtype):
        from ..core.dist import Dist
        from ..redist.engine import redistribute
        CIRC, VC, STAR = Dist.CIRC, Dist.VC, Dist.STAR

        def fn(a):
            A = _as_dm(a, grid, n, n)
            B = redistribute(A, CIRC, CIRC)
            return redistribute(B, VC, STAR)
        return fn, (_mcmr_input(grid, n, n, dtype),), {}
    return DriverSpec(f"redist_circ_{variant}" if variant
                      else "redist_circ", build)


#: trace-time panel-implementation override (ISSUE 17): the comm-plan
#: invariance gate re-traces every factorization variant with the fused
#: Pallas panels selected and byte-compares against the goldens.  A
#: module global (read INSIDE the traced fn, at trace time) rather than
#: a spec parameter, so the registry -- and therefore every golden doc's
#: meta -- is unchanged: the override is an assertion harness, not a
#: new driver variant.
_PANEL_IMPL_OVERRIDE = None


def _panel_impl():
    return _PANEL_IMPL_OVERRIDE


@contextlib.contextmanager
def panel_impl_override(impl):
    """Trace the factorization drivers with ``panel_impl=impl`` (e.g.
    'pallas') without touching their registered meta.  Used by the
    ``tools/check.sh kernels`` gate and tests/kernels to pin that panel
    kernels are replicated-local: every comm plan must stay
    byte-identical under the override."""
    global _PANEL_IMPL_OVERRIDE
    prev = _PANEL_IMPL_OVERRIDE
    _PANEL_IMPL_OVERRIDE = impl
    try:
        yield
    finally:
        _PANEL_IMPL_OVERRIDE = prev


def _cholesky_spec(variant, lookahead, crossover, comm_precision=None,
                   abft=False):
    def build(grid, n, nb, dtype):
        from ..lapack.cholesky import cholesky

        def fn(a):
            return cholesky(_as_dm(a, grid, n, n), nb=nb,
                            lookahead=lookahead, crossover=crossover,
                            comm_precision=comm_precision,
                            abft=abft or None, panel_impl=_panel_impl())
        meta = {"lookahead": lookahead, "crossover": crossover,
                "comm_precision": comm_precision, "abft": abft}
        return fn, (_mcmr_input(grid, n, n, dtype),), meta
    # commq variants intentionally move bf16 on the wire (EL005 opt-in)
    return DriverSpec(f"cholesky_{variant}", build,
                      allow_bf16=comm_precision is not None)


def _lu_spec(variant, lookahead, crossover, panel="classic",
             comm_precision=None, abft=False):
    def build(grid, n, nb, dtype):
        from ..lapack.lu import lu

        def fn(a):
            return lu(_as_dm(a, grid, n, n), nb=nb,
                      lookahead=lookahead, crossover=crossover, panel=panel,
                      comm_precision=comm_precision, abft=abft or None,
                      panel_impl=_panel_impl())
        meta = {"lookahead": lookahead, "crossover": crossover,
                "panel": panel, "comm_precision": comm_precision,
                "abft": abft}
        return fn, (_mcmr_input(grid, n, n, dtype),), meta
    return DriverSpec(f"lu_{variant}", build,
                      allow_bf16=comm_precision is not None)


def _qr_spec(variant="", panel="classic", abft=False):
    def build(grid, n, nb, dtype):
        from ..lapack.qr import qr

        def fn(a):
            return qr(_as_dm(a, grid, n, n), nb=nb, panel=panel,
                      abft=abft or None, panel_impl=_panel_impl())
        # the abft key is CONDITIONAL so the pre-ISSUE-15 qr / qr_tsqr
        # golden docs stay byte-identical (to_doc merges meta verbatim)
        meta = {"panel": panel, **({"abft": True} if abft else {})}
        return fn, (_mcmr_input(grid, n, n, dtype),), meta
    return DriverSpec(f"qr_{variant}" if variant else "qr", build)


def _registry() -> dict:
    specs = [
        _gemm_spec("A"), _gemm_spec("B"), _gemm_spec("C"),
        _gemm_spec("dot"), _gemm_spec("gspmd"), _gemm_slice_spec(),
        _trsm_spec(),
        _herk_spec(),
        # classic = right-looking baseline; lookahead = pure pipeline
        # (crossover disabled); crossover = pipeline + tail collapse
        _cholesky_spec("classic", lookahead=False, crossover=0),
        _cholesky_spec("lookahead", lookahead=True, crossover=0),
        _cholesky_spec("crossover", lookahead=True, crossover=DEFAULT_XOVER),
        _lu_spec("classic", lookahead=False, crossover=0),
        _lu_spec("lookahead", lookahead=True, crossover=0),
        _lu_spec("crossover", lookahead=True, crossover=DEFAULT_XOVER),
        # calu = ISSUE 6's tournament-pivoted panel on the default
        # pipelined (lookahead + crossover-tail) schedule; the one-psum
        # row-block solve replaces the classic all_to_all + all_gather
        # pair, so its plan must stay strictly smaller than both
        # lu_classic AND lu_crossover (pinned via CALU_PAIRS)
        _lu_spec("calu", lookahead=True, crossover=DEFAULT_XOVER,
                 panel="calu"),
        _qr_spec(),
        _qr_spec("tsqr", panel="tsqr"),
        # commq = ISSUE 8's quantized-wire twins: the SAME schedule knobs
        # as the baseline variant plus comm_precision='bf16', so the
        # golden pair pins the EQuARX win exactly -- identical collective
        # round counts, ~half the estimated wire bytes (COMMQ_PAIRS)
        _lu_spec("calu_commq", lookahead=True, crossover=DEFAULT_XOVER,
                 panel="calu", comm_precision="bf16"),
        _cholesky_spec("lookahead_commq", lookahead=True, crossover=0,
                       comm_precision="bf16"),
        # abft = ISSUE 11's checksum-guarded drivers: the classic
        # right-looking schedule (abft= forces it) plus the per-panel
        # checksum maintenance, traced with the guard's host checks
        # inert -- the golden pins the ABFT-enabled collective structure
        # so checksum overhead changes are a reviewed diff
        _lu_spec("abft", lookahead=False, crossover=0, abft=True),
        _cholesky_spec("abft", lookahead=False, crossover=0, abft=True),
        # qr_abft = ISSUE 15's guarded QR: the same blocked Householder
        # schedule plus the checksum reductions (panel gathers unchanged,
        # one extra [MC,MR] panel write already shared with the plain
        # sweep) -- pins the guarded collective structure like lu_abft
        _qr_spec("abft", abft=True),
        # direct = ISSUE 12's one-shot redistribution twins: the SAME
        # schedule knobs as the baseline variant plus redist_path=
        # 'direct', so the golden pair pins the plan-compiler win exactly
        # -- the chained operand moves (3 hops for the A/B operand
        # relands, 2 for dot's cyclic ones) collapse into a single
        # all_to_all on multi-chip grids (DIRECT_PAIRS)
        _gemm_spec("A", variant="direct", redist_path="direct"),
        _gemm_spec("B", variant="direct", redist_path="direct"),
        _gemm_spec("dot", variant="direct", redist_path="direct"),
        # ISSUE 13: every remaining driver family gets a one-shot twin.
        # qr's own panel gathers are already single-round, so the lq
        # entry transpose (a 3-hop chain) carries the qr-family pin;
        # trsm's win is the side='R' entry/exit transposes; herk's is the
        # per-panel [VC,STAR]+spread pair collapsing into ONE exchange.
        _lq_spec(),
        _lq_spec(variant="direct", redist_path="direct"),
        _trsm_spec(variant="r", side="R"),
        _trsm_spec(variant="r_direct", side="R", redist_path="direct"),
        _herk_spec(variant="direct", redist_path="direct"),
        # ragged [MD,*] round-trip: equal round counts chain vs direct,
        # so NOT in DIRECT_PAIRS -- its golden pins the ragged-slot BYTE
        # drop instead (trimmed slots + subgroup packing vs the padded
        # full-mesh exchange; see tests/analysis/test_direct_plan.py)
        _redist_md_spec(),
        _redist_md_spec(variant="direct", redist_path="direct"),
        # ISSUE 14: the CIRC endpoints folded into the jitted shard_map
        # path -- the round-trip traces abstractly (impossible with the
        # old eager bridge) and its golden pins the fused gather rounds
        _redist_circ_spec(),
    ]
    out = {}
    for s in specs:
        factor = MEM_BUDGET_FACTORS.get(s.name)
        if factor is not None:
            s = dataclasses.replace(s, mem_budget_factor=factor)
        out[s.name] = s
    return out


#: per-driver EL006 overrides above the 4.0x default, each a DECLARED
#: memory cost the variant is known to pay (measured on 1x1+2x2, pinned
#: by the memory_plan goldens + tests/analysis/test_mem_lint.py):
#: `[CIRC,CIRC]` and `[MD,*]` forms concentrate the operand on few
#: devices, and the direct one-shot plans stage full send+recv buffers
#: at once.
MEM_BUDGET_FACTORS = {
    "gemm_dot_direct": 5.0,   # replicated-form staging, direct plans
    "herk_direct": 6.0,
    "qr_lq_direct": 5.0,
    "redist_circ": 6.5,       # root holds the FULL gathered operand
    "redist_md": 7.5,         # lcm-stride staging buffers
    "redist_md_direct": 7.5,
}

DRIVERS = _registry()

#: look-ahead/classic pairs at EQUAL n/nb whose all_gather rounds the
#: golden tests compare: the default look-ahead configuration (crossover
#: tail enabled) must issue STRICTLY FEWER rounds than classic -- the
#: jaxpr-level pin of the PR 1-2 fusions.
LOOKAHEAD_PAIRS = (
    ("cholesky_crossover", "cholesky_classic"),
    ("lu_crossover", "lu_classic"),
)

#: CALU pins (ISSUE 6): at equal n/nb (equal panel count) the tournament-
#: pivoted schedule must issue strictly fewer collective rounds than the
#: classic partial-pivot baseline AND than the pipelined classic-panel
#: default -- i.e. strictly fewer rounds PER PANEL.  (calu variant,
#: classic-panel comparison variants.)
CALU_PAIRS = (
    ("lu_calu", ("lu_classic", "lu_crossover")),
)

#: quantized-wire pairs (ISSUE 8): (commq variant, full-precision twin) at
#: IDENTICAL schedule knobs.  The golden tests pin, per pair on the 2x2
#: grid: equal per-collective round counts and >= COMMQ_MIN_BYTE_RATIO x
#: lower total estimated wire bytes -- the jaxpr-level proof that the
#: comm_precision knob halves bytes without adding rounds.
COMMQ_PAIRS = (
    ("lu_calu_commq", "lu_calu"),
    ("cholesky_lookahead_commq", "cholesky_lookahead"),
)
COMMQ_MIN_BYTE_RATIO = 1.9

#: one-shot redistribution pairs (ISSUE 12): (direct variant, chained
#: twin) at IDENTICAL schedule knobs.  The golden tests pin, per pair on
#: the 2x2 grid: STRICTLY FEWER total collective rounds for the direct
#: variant (the multi-hop operand relands collapse into one all_to_all);
#: on 1x1 every plan is 'local', so the direct variant issues no
#: collectives at all (<= the chain's degenerate 1-participant rounds).
DIRECT_PAIRS = (
    ("gemm_a_direct", "gemm_a"),
    ("gemm_b_direct", "gemm_b"),
    ("gemm_dot_direct", "gemm_dot"),
    # ISSUE 13: the qr/trsm/herk one-shot twins (redist_md is pinned on
    # bytes, not rounds -- its chain and direct round counts tie)
    ("qr_lq_direct", "qr_lq"),
    ("trsm_r_direct", "trsm_r"),
    ("herk_direct", "herk"),
)


def driver_names() -> list:
    return sorted(DRIVERS)


def trace_driver(name: str, grid: Grid, n: int = DEFAULT_N,
                 nb: int = DEFAULT_NB, dtype=jnp.float32):
    """Abstractly trace a registered driver; return
    ``(CommPlan, closed_jaxpr, redist_log)``.

    Pure trace: no device buffers are created and nothing executes, so
    this runs identically under ``JAX_PLATFORMS=cpu`` on any host.  The
    grid's devices only parameterize the mesh metadata.
    """
    spec = DRIVERS.get(name)
    if spec is None:
        raise KeyError(f"unknown driver {name!r}; known: {driver_names()}")
    fn, args, meta = spec.build(grid, n, nb, dtype)
    with redist_counts():                      # isolate the global counter
        with redist_trace() as log:
            closed = jax.make_jaxpr(fn)(*args)
    events = collect_events(closed)
    full_meta = {"n": n, "nb": nb, "dtype": jnp.dtype(dtype).name,
                 "input_dtypes": [jnp.dtype(a.dtype).name for a in args],
                 "allow_bf16": spec.allow_bf16}
    full_meta.update(meta)
    plan = plan_from_parts(name, (grid.height, grid.width), full_meta,
                           events, log)
    return plan, closed, log


def trace_callable(fn, args, name: str = "custom", grid=None, meta=None):
    """Trace an arbitrary driver callable (used by tests and the linter's
    seeded-regression harness).  ``args`` are ShapeDtypeStructs (or
    arrays); returns ``(CommPlan, closed_jaxpr, redist_log)``."""
    with redist_counts():
        with redist_trace() as log:
            closed = jax.make_jaxpr(fn)(*args)
    events = collect_events(closed)
    gshape = (grid.height, grid.width) if grid is not None else (0, 0)
    full_meta = {"input_dtypes": [jnp.dtype(a.dtype).name for a in args]}
    full_meta.update(meta or {})
    plan = plan_from_parts(name, gshape, full_meta, events, log)
    return plan, closed, log
