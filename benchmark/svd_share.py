"""Seconds a solve the dense SVD spends in each of its stages: the timed
ops by the segments their ``op_name`` path holds after ``el.svd``
(grammar: ``elemental_tpu/obs/__init__.py``):

* ``qdwh_qr`` / ``qdwh_chol``: under ``el.polar/qdwh_qr<steps>`` /
  ``el.polar/qdwh_chol<steps>``, the steps of the polar iteration by
  variant (``<steps>`` is ``<first>`` or ``<first>_<last>``, numbered from
  01: consecutive steps of one variant may be ONE loop body; ``by_step``
  keeps the numbered segments apart);
* ``polar_h``: under ``el.polar/polar_h``, the product ``H = U_p^T A``;
* ``polar_rest``: under ``el.polar`` and none of those (the scale, the
  degenerate case's selects);
* ``svd_u``: under ``svd_u``, the product ``U = U_p V``;
* ``eig``: under the inner ``el.herm_eig``;
* ``svd_rest``: under ``el.svd`` and none of the above (the reversal of
  the spectrum).

``scopes.py``'s rule gives a nested driver's op its OWN phase
(``qr/panel``, ``cholesky/update``, ``trsm/solve``), whatever stands over
it, which is what ``panel_share`` and ``update_share`` read in this cell
too; this file reads what stands over it.  SELF time, mean over the
devices, an op's path found as ``scopes.Module`` finds its class (a fusion
takes its own path, else its root's, else that of the class most of its
fused instructions carry).  The five readers of the polar stage share it
(``layer_metrics/polar_share.py``, ``qdwh_qr_share.py``,
``qdwh_chol_share.py``, ``svd_eig_share.py``, ``polar_mxu_util.py``).
This file imports nothing of the program.
"""
import json
import re

import scopes
from lstsq_share import busy_a_solve

OPERATOR = "svd"
#: the keys of the polar stage: ``el.polar`` and the two outer products
POLAR = ("qdwh_qr", "qdwh_chol", "polar_h", "polar_rest", "svd_u")
STAGES = POLAR + ("eig", "svd_rest")

_STEP = re.compile(r"^(qdwh_qr|qdwh_chol)\d+(_\d+)?$")


def op_path(module, instruction):
    """The ``op_name`` path that gives an instruction its class as
    ``scopes.Module`` classes it."""
    own = module.paths[instruction]
    if scopes.classify(own)[0] != scopes.UNSCOPED \
            or instruction not in module.calls:
        return own
    found = module.instruction_class(instruction)
    fused = [(module.paths[n], root)
             for n, root in module.members[module.calls[instruction]]
             if scopes.classify(module.paths[n]) == found]
    if found[0] == scopes.UNSCOPED or not fused:
        return own
    return next((p for p, root in fused if root), fused[0][0])


def stage_of(path):
    """``(stage, numbered segment or None)`` of a path, or ``(None, None)``
    where no ``el.svd`` stands in it."""
    segs = path.split("/")
    if "el.svd" not in segs:
        return None, None
    for i, seg in enumerate(segs):
        if seg == "el.polar":
            for inner in segs[i + 1:]:
                step = _STEP.match(inner)
                if step:
                    return step.group(1), inner
                if inner == "polar_h":
                    return "polar_h", None
            return "polar_rest", None
        if seg == "svd_u":
            return "svd_u", None
        if seg == "el.herm_eig":
            return "eig", None
    return "svd_rest", None


def stage_seconds(module, trace):
    """``({stage: seconds a solve}, {numbered step: seconds a solve})``,
    mean over the devices; ops outside ``el.svd`` are in neither."""
    devices = trace["devices"].values()
    by_stage = {key: 0.0 for key in STAGES}
    by_step = {}
    where = {}                      # instruction -> stage_of its path
    for d in devices:
        for name, self_ns in scopes.self_times(d["timed_ops"]):
            instruction = scopes.event_instruction(name)
            if instruction not in where:
                where[instruction] = stage_of(op_path(module, instruction))
            stage, step = where[instruction]
            if stage is None:
                continue
            seconds = self_ns * 1e-9 / d["n_timed"] / len(devices)
            by_stage[stage] += seconds
            if step:
                by_step[step] = by_step.get(step, 0.0) + seconds
    return by_stage, by_step


_CACHE = []          # [(trace, seconds by stage)]: one traced window a process


def summary(trace, run):
    """The stages' seconds a solve in a cell that runs ``svd``, or None:
    under another operator, and where the program names no ``el.svd``
    scope.  Prints its line once."""
    facts = run["facts"]
    if facts.get("operator") != OPERATOR:
        return None
    for cached_trace, cached in _CACHE:
        if cached_trace is trace:
            return cached
    module = scopes._module_of(scopes.module_texts(facts["solve_module"]),
                               trace)
    result = None
    if any("el.svd" in path.split("/") for path in module.paths.values()):
        result, by_step = stage_seconds(module, trace)
        print(json.dumps({
            "svd_stages": "seconds a solve by stage of the SVD (op_name "
                          "segments after el.svd), mean over the devices",
            "seconds": result,
            "by_step": dict(sorted(by_step.items())),
            "busy_a_solve": busy_a_solve(trace)}), flush=True)
    _CACHE.append((trace, result))
    return result


def seconds(trace, run, keys):
    """Summed seconds a solve under the named stages, or None as above."""
    result = summary(trace, run)
    if result is None:
        return None
    return sum(result[key] for key in keys)


def read_share(trace, run, keys):
    """The share (%) of the timed busy time under the named stages."""
    found = seconds(trace, run, keys)
    if found is None:
        return None
    return 100.0 * found / busy_a_solve(trace)
