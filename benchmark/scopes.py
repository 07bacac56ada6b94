"""Device time of the timed solves by the phase names the program gives
its ops (``jax.named_scope``: ``el.<driver>/k<step>/<phase>``,
``el.redist.<SRC>.to.<DST>``, ``factor`` / ``sweeps`` of a public solve;
the grammar is in ``elemental_tpu/obs/__init__.py``).

Three parts, kept apart so each can be tested on hand-made input:

* the lookup: the optimized HLO text of the loaded executable named
  ``facts["solve_module"]``, through the client's list of live
  executables -- no compile request, no array operation;
* the text: ``instruction -> op_name path`` and, for a fusion, the
  computation it calls; a path's class; an instruction's class (a fusion
  takes its own, else its root's, else the class most of its fused
  instructions carry);
* the arithmetic: SELF time per class over ``(name, start, dur)`` events
  (an event that encloses others -- a ``while``, a ``conditional`` -- is
  charged only what they do not cover).

``summary(trace, run)`` joins them, prints one line with the seconds per
``<driver>/<phase>`` and per ``el.redist.*`` name, and is cached per
process.  Where the program names nothing (a parent commit without
scopes) it returns None and every reader built on it reports nothing.
This file imports nothing of the program.
"""
import json
import re

#: classes a path can fall into besides a phase name
REDIST, SWEEP, OTHER, UNSCOPED = "redist", "sweep", "other", "unscoped"

_STEP = re.compile(r"^k\d{2,}$")
#: ``%name = ...`` or ``ROOT %name = ...`` at the start of an instruction
_INSTR = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s")
#: ``%name (params) -> shape {`` or ``ENTRY %name (...) -> ... {``
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")


# ------------------------------------------------------------------ lookup

def module_texts(module_name):
    """Optimized HLO text of every live executable's module of that name
    (a session rebuilt from the compile cache may leave two)."""
    import jax
    texts = []
    for executable in jax.devices()[0].client.live_executables():
        for module in executable.hlo_modules():
            if module.name == module_name:
                texts.append(module.to_string())
    return texts


# -------------------------------------------------------------------- text

def parse_hlo(text):
    """``({instruction: op_name path}, {instruction: called computation},
    {computation: [(instruction, is_root)]})`` of an HLO module's text."""
    paths, calls, members = {}, {}, {}
    current = None
    for line in text.split("\n"):
        m = _INSTR.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c is not None:
                current = members.setdefault(c.group(1), [])
            continue
        name = m.group(2)
        op = _OP_NAME.search(line)
        paths[name] = op.group(1) if op else ""
        called = _CALLS.search(line)
        if called and " fusion(" in line:
            calls[name] = called.group(1)
        if current is not None:
            current.append((name, bool(m.group(1))))
    return paths, calls, members


def classify(path):
    """``(class, detail)`` of an op_name path.  Any ``el.redist.`` segment
    -> ``redist``; no ``el.`` segment -> ``unscoped``; under a public
    solve's ``sweeps`` -> ``sweep``; else the phase after the first
    ``k<step>``; an ``el.`` scope with no phase -> ``other``.  ``detail``
    is ``<driver>/<phase>`` (``<driver>/-`` outside a phase), the
    ``el.redist.*`` name, or ``unscoped``."""
    segs = path.split("/")
    scoped = [s for s in segs if s.startswith("el.")]
    if not scoped:
        return UNSCOPED, UNSCOPED
    for s in scoped:
        if s.startswith("el.redist."):
            return REDIST, s
    step = next((i for i, s in enumerate(segs) if _STEP.match(s)), None)
    phase = segs[step + 1] if step is not None and step + 1 < len(segs) \
        else None
    before = segs[:step] if step is not None else segs
    driver = [s for s in before if s.startswith("el.")][-1][3:]
    detail = f"{driver}/{phase or '-'}"
    if any(s == "sweeps" and i and segs[i - 1].startswith("el.")
           for i, s in enumerate(segs)):
        return SWEEP, detail
    return (phase, detail) if phase else (OTHER, detail)


class Module:
    """One module's instructions and the class of each."""

    def __init__(self, text):
        self.paths, self.calls, self.members = parse_hlo(text)
        self.scoped = any("el." in p for p in self.paths.values())
        self._class = {}

    def __contains__(self, name):
        return name in self.paths

    def instruction_class(self, name):
        """``(class, detail)`` of an instruction: its own path's; a fusion
        whose own path names no scope takes its root's, else the class
        most of its fused instructions carry."""
        found = self._class.get(name)
        if found is None:
            found = classify(self.paths[name])
            if found[0] == UNSCOPED and name in self.calls:
                found = self._fused_class(self.calls[name])
            self._class[name] = found
        return found

    def _fused_class(self, computation):
        inside = [(classify(self.paths[n]), root)
                  for n, root in self.members.get(computation, ())]
        named = [(c, root) for c, root in inside if c[0] != UNSCOPED]
        for c, root in named:
            if root:
                return c
        if not named:
            return UNSCOPED, UNSCOPED
        votes = {}
        for c, _root in named:
            votes[c] = votes.get(c, 0) + 1
        return max(votes.items(), key=lambda kv: kv[1])[0]


# -------------------------------------------------------------- arithmetic

def self_times(events):
    """``[(name, self duration)]`` of ``(name, start, dur)`` events of one
    line: an event that encloses later ones is charged its duration less
    that of the events directly inside it."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    covered = [0.0] * len(events)
    stack = []                                  # indices of open events
    for i in order:
        _name, start, dur = events[i]
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            covered[stack[-1]] += dur
        stack.append(i)
    return [(events[i][0], max(events[i][2] - covered[i], 0.0))
            for i in range(len(events))]


def event_instruction(event_name):
    """The instruction an op event stands for: the first word of its
    shortened name (``fusion.3 f32[64,64]`` -> ``fusion.3``)."""
    return event_name.split(" ", 1)[0].lstrip("%")


def class_seconds(module, events):
    """``({class: seconds}, {detail: seconds})`` of one device's timed op
    events (nanoseconds in, seconds out); raises if an event's
    instruction is not in the module's text."""
    by_class, by_detail = {}, {}
    for name, self_ns in self_times(events):
        instruction = event_instruction(name)
        if instruction not in module:
            raise LookupError(
                f"timed op {name!r} is not an instruction of the module "
                f"the scopes were read from: wrong module")
        cls, detail = module.instruction_class(instruction)
        by_class[cls] = by_class.get(cls, 0.0) + self_ns * 1e-9
        by_detail[detail] = by_detail.get(detail, 0.0) + self_ns * 1e-9
    return by_class, by_detail


# ----------------------------------------------------------------- summary

def summarize(module, trace):
    """``{"share": {class: % of timed busy time, mean over the devices},
    "seconds": {detail: seconds a solve, mean over the devices},
    "sum": the shares' sum}``."""
    devices = trace["devices"].values()
    share, seconds = {}, {}
    for d in devices:
        by_class, by_detail = class_seconds(module, d["timed_ops"])
        for cls, s in by_class.items():
            share[cls] = share.get(cls, 0.0) \
                + 100.0 * s / d["timed_busy_s"] / len(devices)
        for detail, s in by_detail.items():
            seconds[detail] = seconds.get(detail, 0.0) \
                + s / d["n_timed"] / len(devices)
    return {"share": share, "seconds": seconds, "sum": sum(share.values())}


def _module_of(texts, trace):
    """The first text that holds every timed op's instruction; failing
    that the first text, so that ``class_seconds`` names the stray op."""
    if not texts:
        raise LookupError("no live executable holds the timed module")
    modules = [Module(text) for text in texts]
    for module in modules:
        if all(event_instruction(name) in module
               for d in trace["devices"].values()
               for name, _start, _dur in d["timed_ops"]):
            return module
    return modules[0]


_CACHE = []          # [(trace, summary)]: one traced window per process


def summary(trace, run):
    """The summary of this process's traced window, or None where the
    program names no scope; prints its line once."""
    for cached_trace, cached in _CACHE:
        if cached_trace is trace:
            return cached
    name = run["facts"].get("solve_module")
    if name is None:                  # the run names no timed module
        return None
    module = _module_of(module_texts(name), trace)
    result = summarize(module, trace) if module.scoped else None
    _CACHE.append((trace, result))
    if result is not None:
        ranked = sorted(result["seconds"].items(), key=lambda kv: -kv[1])
        print(json.dumps({
            "scopes": "seconds a solve by <driver>/<phase> and by "
                      "el.redist.* name, mean over the devices",
            "seconds": dict(ranked),
            "share_percent": dict(sorted(result["share"].items())),
            "share_sum_percent": result["sum"]}), flush=True)
    return result


def share(trace, run, classes):
    """Summed share (%) of the named classes, or None without scopes."""
    result = summary(trace, run)
    if result is None:
        return None
    return sum(result["share"].get(c, 0.0) for c in classes)
