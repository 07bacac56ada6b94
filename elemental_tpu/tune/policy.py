"""Knob resolution policy: cache first, cost model second, explicit wins.

This is the driver-facing face of the subsystem.  A driver that receives
``'auto'`` for a knob calls :func:`resolve_knobs`; the resolver

  1. pins every knob the caller passed EXPLICITLY (an explicit value --
     including ``None``, the "driver default" sentinel -- always wins and
     simply constrains the candidate space),
  2. consults the persistent :mod:`.cache` for a measured winner under the
     ``(op, shape-bucket, dtype, grid, backend)`` key,
  3. otherwise scores the legal candidates with the analytic
     :mod:`.cost_model` (abstract traces + roofline; no device execution,
     so ``'auto'`` works cold on any machine) and picks the cheapest.

Resolutions are memoized in-process per (key, pinned-knobs, cache-dir), so
the hot path after the first call is one dict lookup.  The canonical
:func:`blocksize_policy` also lives here -- the single grain-rounding /
extent-clamping rule every blocked driver shares (re-exported as
``elemental_tpu.blas.level3._blocksize`` for its historical importers).
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

from . import cache as _cache
from .knobs import OPS, TuneContext, candidate_configs


# ---------------------------------------------------------------------
# the canonical blocksize policy (one rule, every driver)
# ---------------------------------------------------------------------

def blocksize_policy(nb, grain: int, extent: int) -> int:
    """Resolve an ``nb`` request to a legal block size: ``None`` reads the
    global :func:`~elemental_tpu.core.environment.blocksize` stack, the
    result is rounded up to the distribution ``grain`` (views must start
    and end on stride boundaries) and clamped to the grain-rounded
    ``extent``.  ``'auto'`` must already have been resolved by
    :func:`resolve_knobs` -- reaching here with a string is a driver bug.
    """
    if isinstance(nb, str):
        raise TypeError(f"nb={nb!r} reached blocksize_policy unresolved; "
                        "drivers must route 'auto' through tune.resolve_knobs")
    from ..core.view import round_up
    if nb is None:
        from ..core.environment import blocksize
        nb = blocksize()
    nb = round_up(max(nb, 1), grain)
    return min(nb, round_up(max(extent, 1), grain))


#: float32 block of a stage at size on one chip (PERF.md 4).  ``reduce``:
#: the Hermitian reduction and its back-transform, whose panel is a
#: column-at-a-time loop, latency- and HBM-bound: narrow, what
#: ``heig.1x1.b2b`` measured.  ``block``: the stages whose panel is itself
#: blocked (Cholesky, the triangular sweeps, the rank-k accumulations): the
#: MXU's width, what the ``hpd`` cells measured.  ``qr``: the blocked
#: Householder QR and the reflector sweep that must share its blocking; its
#: panel is a column loop too, but each panel is unrolled into the program
#: twice (the factorization, the reflector sweep), and at 256 the SVD
#: cell's program passed the compile cache's limit on one entry
#: (``perf/program_size.py``).
STAGE_BLOCKS = {"reduce": 256, "qr": 512, "block": 2048}


def stage_blocksize(stage: str, extent: int, grid, dtype) -> int:
    """The block a COMPOSED driver (``svd``, ``polar``) hands one of its
    stages when its caller passed ``nb=None``: the stages of one call want
    different blocks, and one global default (128) serves none of them at
    size.  ``stage`` is a key of :data:`STAGE_BLOCKS`; the float32 value is
    scaled by the dtype's width (the same bytes a panel), kept to at most
    an eighth of the stage's ``extent`` (eight steps or more) and to 128
    at least, and rounded up to the grid's grain.  The stage's own
    :func:`blocksize_policy` still clamps it to the extent."""
    from ..core.view import round_up
    target = STAGE_BLOCKS[stage] * 4 // max(np.dtype(dtype).itemsize, 4)
    nb = max(128, min(target, int(extent) // 8))
    return round_up(nb, math.lcm(grid.height, grid.width))


# ---------------------------------------------------------------------
# resolution
# ---------------------------------------------------------------------

@dataclasses.dataclass
class Resolution:
    """The outcome of one knob resolution."""
    op: str
    key: _cache.CacheKey
    source: str                  # "cache" | "cost_model"
    config: dict                 # values for the knobs that were 'auto'
    requested: dict              # the original knob request
    scores: list | None = None   # CostBreakdowns (cost-model path only)

    def to_doc(self) -> dict:
        return {"op": self.op, "key": self.key.filename(),
                "source": self.source, "config": dict(self.config),
                "requested": {k: str(v) if isinstance(v, str) else v
                              for k, v in self.requested.items()}}


_RESOLVE_MEMO: dict = {}


def clear_memo() -> None:
    """Drop the in-process resolution memo (tests swap cache dirs)."""
    _RESOLVE_MEMO.clear()
    from . import cost_model
    cost_model.clear_trace_memo()


def is_auto(value) -> bool:
    return isinstance(value, str) and value == "auto"


def wants_auto(*values) -> bool:
    return any(is_auto(v) for v in values)


def _context(op: str, dims, dtype, grid) -> TuneContext:
    import jax.numpy as jnp
    backend = grid.mesh.devices.flat[0].platform
    return TuneContext(op=op, dims=tuple(int(d) for d in dims),
                       dtype=jnp.dtype(dtype).name,
                       grid_shape=(grid.height, grid.width), backend=backend)


def resolve(op: str, *, gshape, dtype, grid, requested: dict,
            machine=None) -> Resolution:
    """Resolve the ``'auto'`` knobs of one driver call.

    ``gshape`` is the op's dim tuple ((n, n), (m, n), or gemm's
    (m, k, n)); ``requested`` maps every tunable knob to its requested
    value -- ``'auto'`` entries get resolved, anything else is pinned.
    """
    spec = OPS.get(op)
    if spec is None:
        raise KeyError(f"unknown tunable op {op!r}; known: {sorted(OPS)}")
    ctx = _context(op, gshape, dtype, grid)
    auto_keys = tuple(k for k, v in requested.items() if is_auto(v))
    # non-'auto' values pin their knob -- INCLUDING None, the "driver
    # default" sentinel (blocksize stack / schedule defaults), so a user
    # asking only alg='auto' never gets an nb-assuming alg choice
    pinned = {k: v for k, v in requested.items() if not is_auto(v)}
    key = _cache.make_key(op, ctx.dims, ctx.dtype, ctx.grid_shape,
                          ctx.backend)
    memo_key = (key, tuple(sorted(pinned.items(), key=repr)), auto_keys,
                _cache.cache_dir(), None if machine is None else machine.name)
    hit = _RESOLVE_MEMO.get(memo_key)
    if hit is not None:
        return hit

    res = None
    entry = _cache.load(key)
    if entry is not None:
        cfg = entry["config"]
        if all(k in cfg for k in auto_keys):
            res = Resolution(op=op, key=key, source="cache",
                             config={k: cfg[k] for k in auto_keys},
                             requested=dict(requested))
    if res is None:
        import jax.numpy as jnp
        from . import cost_model
        cands = candidate_configs(ctx, pinned)
        if not cands:
            raise ValueError(f"no legal {op} configuration for {requested} "
                             f"at dims {ctx.dims} on grid {ctx.grid_shape}")
        scored = [cost_model.score_config(op, cfg, ctx=ctx, grid=grid,
                                          dtype=jnp.dtype(dtype),
                                          machine=machine)
                  for cfg in cands]
        # memory-pruned candidates (statically derived peak over the
        # backend HBM, ISSUE 18) sort behind every fitting one: an OOM
        # is not a slow configuration.  All-pruned still resolves (the
        # least-bad candidate) so tiny dev grids never hard-fail.
        order = sorted(range(len(scored)),
                       key=lambda i: (scored[i].pruned,
                                      scored[i].total_s, i))
        best = scored[order[0]]
        res = Resolution(op=op, key=key, source="cost_model",
                         config={k: best.config[k] for k in auto_keys
                                 if k in best.config},
                         requested=dict(requested),
                         scores=[scored[i] for i in order])
    _RESOLVE_MEMO[memo_key] = res
    return res


def resolve_knobs(op: str, *, gshape, dtype, grid, knobs: dict,
                  machine=None) -> dict:
    """Driver-facing wrapper: return ``knobs`` with every ``'auto'`` entry
    replaced by the resolved concrete value (other entries pass through
    unchanged -- explicit always wins)."""
    if not wants_auto(*knobs.values()):
        return dict(knobs)
    res = resolve(op, gshape=gshape, dtype=dtype, grid=grid, requested=knobs,
                  machine=machine)
    out = dict(knobs)
    for k in knobs:
        if is_auto(knobs[k]):
            out[k] = res.config.get(k)
    return out


def explain(op: str, *, gshape, dtype, grid, requested: dict | None = None,
            machine=None):
    """(Resolution-like choice, scored candidates sorted best-first) for
    the ``perf.tune explain`` CLI: always runs the cost model (never the
    cache) so the breakdown reflects what a cold resolution would do."""
    import jax.numpy as jnp
    from . import cost_model
    spec = OPS.get(op)
    if spec is None:
        raise KeyError(f"unknown tunable op {op!r}; known: {sorted(OPS)}")
    requested = requested or {k: "auto" for k in spec.knobs}
    ctx = _context(op, gshape, dtype, grid)
    pinned = {k: v for k, v in requested.items() if not is_auto(v)}
    cands = candidate_configs(ctx, pinned)
    scored = sorted((cost_model.score_config(op, cfg, ctx=ctx, grid=grid,
                                             dtype=jnp.dtype(dtype),
                                             machine=machine)
                     for cfg in cands),
                    key=lambda b: (b.pruned, b.total_s))
    return ctx, scored
