"""Seconds a solve the Redistribution layer spends in each PART of its
exchanges: the timed ops whose ``op_name`` path holds an ``el.redist.``
segment (what ``scopes.py`` books as ``redist``), split by the part the
program names under it (grammar in ``elemental_tpu/obs/__init__.py``: the
first of ``pack`` | ``wire`` | ``unpack`` that stands after the first
``el.redist.`` segment):

* ``pack``: the local ops that feed an explicit collective (pad, reshape
  into per-peer blocks, the cast or encode to the wire dtype);
* ``wire``: the explicit collective itself (an async pair's ``-start`` and
  ``-done`` both; SELF time on the op line is what the core waited);
* ``unpack``: the local ops after it (interleave, filter, slice, mask,
  decode); an exchange with no collective is all ``unpack``;
* ``planned``: the remainder, ops under an ``el.redist.`` name that carry
  no part: motion the COMPILER plans (``el.redist.row_permute``) and what
  its passes left without the name of a part.

SELF time, mean over the devices, an op's path found as ``scopes.Module``
finds its class (a fusion takes its own path, else its root's, else that of
the part most of its fused instructions carry).  The four shares sum to
``redist_share``.  Beside the seconds, the BYTES each such op WRITES: the
logical size of its instruction's result in the same optimized HLO text (a
tuple is the sum of its elements; an op in a ``while`` body counts once an
event; an async ``-start`` counts nothing, its ``-done`` writes the result),
so that a relayout's rate can be told from a copy's: ``redist_relayout_gbps``
is the GB the ``pack`` and ``unpack`` ops write a solve over their seconds.
A copy at the HBM roofline writes at most half the published bandwidth.

The readers ``layer_metrics/redist_pack_share.py``, ``redist_wire_share.py``,
``redist_unpack_share.py`` and ``redist_relayout_gbps.py`` share it.  This
file imports nothing of the program and keeps its own copy of the names.
"""
import json
import re

import scopes

PARTS = ("pack", "wire", "unpack")
PLANNED = "planned"

#: ``%name = <shape> opcode(`` of an instruction; the shape is one array or
#: a (nested) tuple of them, layouts and all
_SHAPED = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s([a-z][\w\-]*)\(")
_ARRAY = re.compile(r"\b([a-z]+)(\d+)?(?:e\d+m\d+\w*)?\[([\d,]*)\]")


# -------------------------------------------------------------------- text

def shape_bytes(shape):
    """Logical bytes of an HLO shape's text: every ``dtype[dims]`` in it (a
    tuple's elements summed), ``pred`` a byte, ``token`` and other
    shapeless elements nothing."""
    total = 0
    for kind, bits, dims in _ARRAY.findall(shape):
        if bits:            # c64 is two f32: its number counts both
            width = max(int(bits) // 8, 1)
        else:
            width = 1 if kind == "pred" else 0
        entries = 1
        for d in dims.split(","):
            if d:
                entries *= int(d)
        total += width * entries
    return total


def written_bytes(text):
    """``{instruction: bytes its result holds}`` of an HLO module's text;
    an async ``-start`` writes nothing that its ``-done`` does not."""
    written = {}
    for line in text.split("\n"):
        m = _SHAPED.match(line)
        if m is None:
            continue
        name, shape, opcode = m.groups()
        written[name] = 0 if opcode.endswith("-start") else shape_bytes(shape)
    return written


def part_of(path):
    """``(el.redist.* name, part)`` of a path with an ``el.redist.``
    segment: the first of :data:`PARTS` after the first such segment, or
    :data:`PLANNED`."""
    segs = path.split("/")
    at = next(i for i, s in enumerate(segs) if s.startswith("el.redist."))
    return segs[at], next((s for s in segs[at + 1:] if s in PARTS), PLANNED)


def redist_part(module, instruction):
    """``(el.redist.* name, part)`` of an instruction that ``scopes.Module``
    classes ``redist``, or None where it is of another class.  A fusion
    whose own path names no scope takes its root's part, else the part most
    of its fused instructions of that class carry."""
    found = module.instruction_class(instruction)
    if found[0] != scopes.REDIST:
        return None
    own = module.paths[instruction]
    if scopes.classify(own)[0] != scopes.UNSCOPED:
        return part_of(own)
    fused = [(part_of(module.paths[n]), root)
             for n, root in module.members[module.calls[instruction]]
             if scopes.classify(module.paths[n]) == found]
    for named, root in fused:
        if root:
            return named
    votes = {}
    for named, _root in fused:
        votes[named] = votes.get(named, 0) + 1
    return max(votes.items(), key=lambda kv: kv[1])[0]


# -------------------------------------------------------------- arithmetic

def part_seconds(module, written, trace):
    """``(seconds, share, by_name)``: seconds a solve and share (%) of the
    timed busy time by part (:data:`PARTS` and :data:`PLANNED`), and
    ``{"<el.redist name>/<part>": [seconds, bytes written]}`` a solve, all
    the mean over the devices (the shares as ``scopes.summarize`` takes
    them, so that they sum to its ``redist``)."""
    devices = trace["devices"].values()
    seconds = {key: 0.0 for key in PARTS + (PLANNED,)}
    share = dict(seconds)
    by_name = {}
    where = {}          # instruction -> redist_part, None if not redist
    for d in devices:
        for name, self_ns in scopes.self_times(d["timed_ops"]):
            instruction = scopes.event_instruction(name)
            if instruction not in where:
                where[instruction] = redist_part(module, instruction)
            if where[instruction] is None:
                continue
            hop, part = where[instruction]
            s = self_ns * 1e-9
            seconds[part] += s / d["n_timed"] / len(devices)
            share[part] += 100.0 * s / d["timed_busy_s"] / len(devices)
            entry = by_name.setdefault(f"{hop}/{part}", [0.0, 0.0])
            entry[0] += s / d["n_timed"] / len(devices)
            entry[1] += written.get(instruction, 0) / d["n_timed"] \
                / len(devices)
    return seconds, share, by_name


def relayout_gbps(by_name):
    """GB the ``pack`` and ``unpack`` ops write a solve over their seconds,
    or None where they take no time."""
    local = [v for key, v in by_name.items()
             if key.rsplit("/", 1)[1] in ("pack", "unpack")]
    seconds = sum(v[0] for v in local)
    if seconds <= 0.0:
        return None
    return sum(v[1] for v in local) * 1e-9 / seconds


# ----------------------------------------------------------------- summary

def _module_and_bytes(texts, trace):
    """The module ``scopes._module_of`` picks and its text's bytes."""
    module = scopes._module_of(texts, trace)
    for text in texts:
        written = written_bytes(text)
        if written.keys() >= module.paths.keys():
            return module, written
    return module, {}


_CACHE = []          # [(trace, summary)]: one traced window a process


def summary(trace, run):
    """``{"seconds", "share", "relayout_gbps"}`` of a cell that runs across
    chips, or None: on one chip, where the program names no scope, and
    where NO timed op carries a part (a program from before the parts).
    Prints its line once."""
    facts = run["facts"]
    if facts["chips"] == 1 or facts.get("solve_module") is None:
        return None
    for cached_trace, cached in _CACHE:
        if cached_trace is trace:
            return cached
    module, written = _module_and_bytes(
        scopes.module_texts(facts["solve_module"]), trace)
    result = None
    if module.scoped:
        seconds, share, by_name = part_seconds(module, written, trace)
        if any(seconds[part] > 0.0 for part in PARTS):
            result = {"seconds": seconds, "share": share,
                      "relayout_gbps": relayout_gbps(by_name)}
            ranked = sorted(by_name.items(), key=lambda kv: -kv[1][0])
            print(json.dumps({
                "redist_parts": "seconds a solve in el.redist.* ops by part "
                                "(planned: no part named), mean over the "
                                "devices; by_name: [seconds, GB written, "
                                "GB/s]",
                "seconds": seconds,
                "share_percent": share,
                "relayout_gbps": result["relayout_gbps"],
                "by_name": {key: [s, b * 1e-9, b * 1e-9 / s if s else 0.0]
                            for key, (s, b) in ranked}}), flush=True)
    _CACHE.append((trace, result))
    return result


def read_share(trace, run, part):
    """The share (%) of the timed busy time in one part, or None."""
    result = summary(trace, run)
    return None if result is None else result["share"][part]
