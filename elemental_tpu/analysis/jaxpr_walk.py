"""Recursive jaxpr walker: extract every collective primitive with context.

The core of the static comm-plan analyzer (ISSUE 3): given a closed jaxpr
(from ``jax.make_jaxpr`` over a distributed driver -- tracing only, no
device execution), walk every equation recursively -- into ``jit`` calls,
``shard_map`` bodies, ``scan``/``while``/``cond`` sub-jaxprs, custom-deriv
call jaxprs -- and emit one :class:`CollectiveEvent` per collective
equation encountered, annotated with

  * the mesh axes it communicates over and their total size,
  * the operand shape/dtype and an estimated per-device byte volume
    (ring-algorithm cost model, see :func:`estimate_bytes`),
  * the nesting path (``jit:_redistribute_jit/shard_map``),
  * a static trip-count multiplier (``scan`` lengths compose; ``while``
    bodies are marked non-static since XLA cannot bound them),
  * whether the event sits on a conditional branch.

Scope note: this sees the EXPLICIT collectives of the redistribution
engine (everything issued inside ``shard_map``).  Communication inserted
later by GSPMD for storage-level ops on sharded arrays (e.g. the row-swap
scatters of the LU driver or stationary-A/B storage matmul psums) is a
compile-time decision and is out of scope here -- the plan pins the
schedule the library *chose*, which is what the `[MC,MR]`/`[VC,STAR]`
redistribution algebra controls.
"""
from __future__ import annotations

import dataclasses

from jax.extend import core as jcore
# a nested ``jax.jit`` call is recognised by the primitive OBJECT the
# installed JAX exports, never by its name (which JAX has renamed before)
from jax.extend.core.primitives import jit_p as _JIT_P

#: jaxpr primitive names treated as collectives.
COLLECTIVE_PRIMS = (
    "all_gather",
    "psum",
    "reduce_scatter",
    "ppermute",
    "all_to_all",
)

#: primitives whose sub-jaxpr runs once per loop iteration
_LOOP_PRIMS = ("while", "scan")


@dataclasses.dataclass(frozen=True)
class CollectiveEvent:
    """One collective equation found in the traced program."""
    prim: str                   # one of COLLECTIVE_PRIMS
    axes: tuple                 # mesh axis names communicated over
    axis_size: int              # product of the participating axis sizes
    shape: tuple                # operand (per-device) shape
    dtype: str                  # operand dtype name
    bytes_per_call: int         # estimated per-device bytes moved, one call
    path: tuple                 # nesting scopes from the root jaxpr
    count: int                  # static multiplier (composed scan lengths)
    static: bool                # False under a while loop (unbounded trips)
    conditional: bool           # True on a cond/branch path

    @property
    def total_bytes(self) -> int:
        return self.bytes_per_call * self.count

    def to_doc(self) -> dict:
        d = dataclasses.asdict(self)
        d["axes"] = list(self.axes)
        d["shape"] = list(self.shape)
        d["path"] = list(self.path)
        return d


def estimate_bytes(prim: str, nbytes: int, axis_size: int) -> int:
    """Ring-algorithm per-device traffic estimate for one collective call.

    ``nbytes`` is the operand's local byte size, ``axis_size`` the number
    of participants S.  Formulas (received bytes per device):

      all_gather      nbytes * (S - 1)        (S-1 remote shards land here)
      reduce_scatter  nbytes * (S - 1) / S    (ring reduce-scatter)
      psum            2 * nbytes * (S-1) / S  (reduce-scatter + all-gather)
      all_to_all      nbytes * (S - 1) / S    (keep own shard, swap rest)
      ppermute        nbytes                  (wholesale block move)
    """
    if axis_size <= 1:
        return 0
    if prim == "all_gather":
        return nbytes * (axis_size - 1)
    if prim == "reduce_scatter":
        return nbytes * (axis_size - 1) // axis_size
    if prim == "psum":
        return 2 * nbytes * (axis_size - 1) // axis_size
    if prim == "all_to_all":
        return nbytes * (axis_size - 1) // axis_size
    if prim == "ppermute":
        return nbytes
    return nbytes


def _axis_names(params: dict):
    names = params.get("axis_name", params.get("axes", ()))
    if names is None:
        return ()
    if isinstance(names, (tuple, list)):
        return tuple(str(a) for a in names)
    return (str(names),)


def _axis_size(axes, axis_env: dict, params: dict) -> int:
    groups = params.get("axis_index_groups")
    if groups:
        # grouped collective (all_gather/all_to_all/psum over device
        # subsets): participants = one group's length, not the full axis
        # product -- byte estimates must price the subgroup ring
        return max(1, len(tuple(groups)[0]))
    if "axis_size" in params and params["axis_size"] is not None:
        return int(params["axis_size"])
    size = 1
    for a in axes:
        size *= int(axis_env.get(a, 1))
    return size


def _mesh_axis_sizes(mesh) -> dict:
    try:
        return {str(k): int(v) for k, v in dict(mesh.shape).items()}
    except (AttributeError, TypeError):
        return {}


def _payload_avals(eqn):
    """Every array operand of a collective equation.

    Byte estimates must price the ACTUAL wire payload: each operand with
    its own dtype (a tuple ``psum`` can mix dtypes, and the engine's
    ``comm_precision`` path converts payloads to bfloat16/int8 right
    before the collective -- assuming the driver's input dtype here would
    over-report those by 2-4x)."""
    out = []
    for v in eqn.invars:
        aval = getattr(v, "aval", None)
        if aval is not None and getattr(aval, "shape", None) is not None \
                and getattr(aval, "dtype", None) is not None:
            out.append(aval)
    return out


def _payload_nbytes(avals) -> int:
    total = 0
    for aval in avals:
        n = 1
        for s in aval.shape:
            n *= int(s)
        total += n * aval.dtype.itemsize
    return total


def _sub_jaxprs(val):
    """Yield every (closed or open) jaxpr reachable from a param value."""
    vals = val if isinstance(val, (tuple, list)) else (val,)
    for x in vals:
        if isinstance(x, jcore.ClosedJaxpr):
            yield x.jaxpr
        elif isinstance(x, jcore.Jaxpr):
            yield x


def _scope_label(eqn) -> str:
    name = eqn.params.get("name")
    if eqn.primitive is _JIT_P and name:
        return f"jit:{name}"
    if eqn.primitive.name == "scan":
        return f"scan[{eqn.params.get('length', '?')}]"
    return eqn.primitive.name


def collect_events(closed_jaxpr, axis_env: dict | None = None):
    """Walk ``closed_jaxpr`` recursively; return a list of CollectiveEvent.

    ``axis_env`` optionally seeds mesh axis sizes (normally discovered from
    enclosing ``shard_map`` equations).
    """
    out: list[CollectiveEvent] = []
    jaxpr = closed_jaxpr.jaxpr if isinstance(closed_jaxpr, jcore.ClosedJaxpr) \
        else closed_jaxpr
    _walk(jaxpr, dict(axis_env or {}), (), 1, True, False, out)
    return out


def _walk(jaxpr, axis_env, path, mult, static, conditional, out):
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim in COLLECTIVE_PRIMS:
            axes = _axis_names(eqn.params)
            size = _axis_size(axes, axis_env, eqn.params)
            avals = _payload_avals(eqn)
            shape = tuple(int(s) for s in avals[0].shape) if avals else ()
            dtype = str(avals[0].dtype) if avals else "?"
            nbytes = _payload_nbytes(avals)
            out.append(CollectiveEvent(
                prim=prim, axes=axes, axis_size=size, shape=shape,
                dtype=dtype,
                bytes_per_call=estimate_bytes(prim, nbytes, size),
                path=path, count=mult, static=static,
                conditional=conditional))
            continue
        env = axis_env
        if prim == "shard_map":
            env = dict(axis_env)
            env.update(_mesh_axis_sizes(eqn.params.get("mesh")))
        sub_mult, sub_static = mult, static
        if prim == "scan":
            sub_mult = mult * int(eqn.params.get("length", 1))
        elif prim == "while":
            sub_static = False
        sub_cond = conditional or prim == "cond"
        label = _scope_label(eqn)
        if prim == "cond":
            for i, branch in enumerate(eqn.params.get("branches", ())):
                for sub in _sub_jaxprs(branch):
                    _walk(sub, env, path + (f"cond[{i}]",), sub_mult,
                          sub_static, True, out)
            continue
        for val in eqn.params.values():
            for sub in _sub_jaxprs(val):
                _walk(sub, env, path + (label,), sub_mult, sub_static,
                      sub_cond, out)


def count_pjit_calls(closed_jaxpr, name: str) -> int:
    """Number of nested ``jax.jit`` equations named ``name`` anywhere in the traced
    program -- e.g. ``_redistribute_jit`` / ``_panel_spread_jit`` call
    sites, cross-checkable against the engine's Python-level counters."""
    jaxpr = closed_jaxpr.jaxpr if isinstance(closed_jaxpr, jcore.ClosedJaxpr) \
        else closed_jaxpr
    return _count_pjit(jaxpr, name)


def _count_pjit(jaxpr, name: str) -> int:
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive is _JIT_P and eqn.params.get("name") == name:
            total += 1
        for val in eqn.params.values():
            for sub in _sub_jaxprs(val):
                total += _count_pjit(sub, name)
    return total


# ---------------------------------------------------------------------
# loop-invariant collective detection (lint EL003 support)
# ---------------------------------------------------------------------

def find_loop_invariant_collectives(closed_jaxpr):
    """Collectives inside ``scan``/``while`` bodies whose operands are all
    loop-invariant (derived only from loop constants) -- hoistable out of
    the loop.  Returns a list of ``(prim, path)`` tuples."""
    found: list[tuple] = []
    jaxpr = closed_jaxpr.jaxpr if isinstance(closed_jaxpr, jcore.ClosedJaxpr) \
        else closed_jaxpr
    _scan_loops(jaxpr, (), found)
    return found


def _scan_loops(jaxpr, path, found):
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        label = _scope_label(eqn)
        if prim == "scan":
            body = eqn.params["jaxpr"].jaxpr
            nconsts = int(eqn.params.get("num_consts", 0))
            _check_body(body, nconsts, path + (label,), found)
        elif prim == "while":
            body = eqn.params["body_jaxpr"].jaxpr
            nconsts = int(eqn.params.get("body_nconsts", 0))
            _check_body(body, nconsts, path + (label,), found)
        for val in eqn.params.values():
            for sub in _sub_jaxprs(val):
                _scan_loops(sub, path + (label,), found)


def _check_body(body, nconsts, path, found):
    invariant = set(body.constvars) | set(body.invars[:nconsts])
    for eqn in body.eqns:
        ins_invariant = all(
            not isinstance(v, jcore.Var) or v in invariant
            for v in eqn.invars)
        if eqn.primitive.name in COLLECTIVE_PRIMS and ins_invariant:
            found.append((eqn.primitive.name, path))
        if ins_invariant and str(eqn.primitive.name) not in _LOOP_PRIMS:
            invariant.update(eqn.outvars)
