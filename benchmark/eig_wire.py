"""Seconds a solve the Hermitian eigensolve spends EXCHANGING, by stage: the
timed ops whose ``op_name`` path holds an ``el.redist.`` segment (what
``scopes.py`` books as ``redist``: the collectives and the pack / unpack /
copies beside them) AND lies under one stage of the eigensolve
(``el.hermitian_tridiag``, ``el.tridiag_eig``, ``el.apply_q_herm_tridiag``).
``scopes.py``'s rule gives every such op to one class whatever asked for the
exchange; on a grid that hides which stage the wire costs.  Inside the
reduction the exchanges of the COLUMN loop (the path also holds
``while/body``: the matvec's small dependent collectives, one set a column)
are kept apart from those made once a panel.

SELF time, mean over the devices, an op's path found as ``scopes.Module``
finds its class (a fusion takes its own path, else its root's, else that of
the class most of its fused instructions carry).  The three stages sum to
``redist_share`` less what lies under no stage.  The readers
``layer_metrics/column_wire_share.py``, ``tridiag_wire_share.py``,
``dc_wire_share.py`` and ``backtransform_wire_share.py`` share it.  This
file imports nothing of the program.
"""
import json

import scopes
from lstsq_share import busy_a_solve  # the readers' divisor

STAGES = ("hermitian_tridiag", "tridiag_eig", "apply_q_herm_tridiag")
#: the key of the column loop's exchanges beside the three stages'
COLUMN = "hermitian_tridiag/column"


def redist_path(module, instruction):
    """The ``op_name`` path that makes an instruction a ``redist`` op as
    ``scopes.Module`` classes it, or None where it is of another class."""
    found = module.instruction_class(instruction)
    if found[0] != scopes.REDIST:
        return None
    own = module.paths[instruction]
    if scopes.classify(own)[0] != scopes.UNSCOPED:
        return own
    fused = [(module.paths[n], root)
             for n, root in module.members[module.calls[instruction]]
             if scopes.classify(module.paths[n]) == found]
    return next((p for p, root in fused if root), fused[0][0])


def stage_of(path):
    """``(stage, in the column loop, el.redist.* name)`` of a path with an
    ``el.redist.`` segment: the outermost of the eigensolve's stages that
    stands before the exchange's name, or None where none does."""
    segs = path.split("/")
    upto = next(i for i, s in enumerate(segs) if s.startswith("el.redist."))
    before = segs[:upto]
    stage = next((s[3:] for s in before if s[3:] in STAGES
                  and s.startswith("el.")), None)
    looped = any(a == "while" and b == "body"
                 for a, b in zip(before, before[1:]))
    return stage, stage == STAGES[0] and looped, segs[upto]


def wire_seconds(module, trace):
    """``{stage | COLUMN | "-": seconds a solve}`` and ``{"<stage>/<el.redist
    name>": seconds a solve}`` of the exchanges, mean over the devices;
    ``"-"`` holds those under no stage."""
    devices = trace["devices"].values()
    by_stage = {key: 0.0 for key in STAGES + (COLUMN, "-")}
    by_name = {}
    where = {}          # instruction -> stage_of its path, None if no redist
    for d in devices:
        for name, self_ns in scopes.self_times(d["timed_ops"]):
            instruction = scopes.event_instruction(name)
            if instruction not in where:
                path = redist_path(module, instruction)
                where[instruction] = path and stage_of(path)
            if where[instruction] is None:
                continue
            stage, looped, hop = where[instruction]
            seconds = self_ns * 1e-9 / d["n_timed"] / len(devices)
            by_stage[stage or "-"] += seconds
            if looped:
                by_stage[COLUMN] += seconds
            key = f"{COLUMN if looped else stage or '-'}/{hop}"
            by_name[key] = by_name.get(key, 0.0) + seconds
    return by_stage, by_name


_CACHE = []          # [(trace, seconds by stage)]: one traced window a process


def summary(trace, run):
    """The exchanges' seconds a solve by stage in a cell that runs
    ``herm_eig`` across chips, or None: on one chip, under another operator,
    and where the program names no scope.  Prints its line once."""
    facts = run["facts"]
    if facts.get("operator") != "herm_eig" or facts["chips"] == 1:
        return None
    for cached_trace, cached in _CACHE:
        if cached_trace is trace:
            return cached
    module = scopes._module_of(scopes.module_texts(facts["solve_module"]),
                               trace)
    result = None
    if module.scoped:
        result, by_name = wire_seconds(module, trace)
        print(json.dumps({
            "eig_wire": "seconds a solve in el.redist.* ops by stage of the "
                        "eigensolve (the column loop's apart), mean over "
                        "the devices",
            "seconds": result,
            "by_name": dict(sorted(by_name.items(), key=lambda kv: -kv[1]))}),
            flush=True)
    _CACHE.append((trace, result))
    return result


def read_share(trace, run, key):
    """The share (%) of the timed busy time under one key of ``summary``."""
    result = summary(trace, run)
    if result is None:
        return None
    return 100.0 * result[key] / busy_a_solve(trace)
