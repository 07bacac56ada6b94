"""From the profiler's ``.xplane.pb`` to device busy time, shares and gaps.

Two parts, kept apart so the arithmetic can be tested on hand-made
tuples:

* the reader: ``read_xplane(path)`` -> ``{plane: {line: [(name,
  start_ns, dur_ns), ...]}}`` through ``jax.profiler.ProfileData`` and
  nothing else;
* the interval arithmetic on plain ``(name, start, dur)`` tuples:
  union, clipping to windows, share by name prefix, top names, gaps.

``reduce_trace`` joins them: the device planes, each device's op line
and, from its module line, the windows in which the timed program ran.
"""
import glob
import os
import re

#: plane, op line and module line names of a TPU device in an xplane
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"

COLLECTIVE_PREFIXES = ("all-gather", "all-to-all", "all-reduce",
                       "collective-permute", "reduce-scatter")


# ---------------------------------------------------------------- reader

def find_xplane(trace_dir):
    """The one ``.xplane.pb`` a ``jax.profiler`` trace left under a dir."""
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        raise RuntimeError(f"expected one xplane under {trace_dir}, "
                           f"found {found}")
    return found[0]


def short_name(name):
    """An HLO op event is named by its whole instruction text,
    ``%fusion.3 = f32[30720,30720]{0,1:T(8,128)} fusion(...)``: keep the
    op's own name and the shape it produces, ``fusion.3 f32[30720,30720]``.
    Other names (modules, host events) are kept as they are."""
    if not name.startswith("%") or " = " not in name:
        return name
    op, rest = name[1:].split(" = ", 1)
    shape = re.match(r"\(?[a-z0-9]+\[[0-9,]*\]", rest)
    return f"{op} {shape.group(0).lstrip('(')}" if shape else op


def read_xplane(path):
    """``{plane name: {line name: [(event name, start_ns, dur_ns)]}}``,
    op names shortened by ``short_name``."""
    from jax.profiler import ProfileData
    planes = {}
    for plane in ProfileData.from_file(path).planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (short_name(ev.name), float(ev.start_ns),
                 float(ev.duration_ns)) for ev in line.events)
    return planes


# ---------------------------------------------------- interval arithmetic

def union(intervals):
    """Sorted, merged ``[(start, end)]`` of ``(start, end)`` pairs."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def length(intervals):
    """Total length of the union of ``(start, end)`` pairs."""
    return sum(end - start for start, end in union(intervals))


def spans(events):
    """``(start, end)`` of each ``(name, start, dur)`` event."""
    return [(start, start + dur) for _name, start, dur in events]


def clip(events, windows):
    """The parts of ``(name, start, dur)`` events inside the
    (non-overlapping) ``(start, end)`` windows; an event that straddles a
    window's edge is cut at it."""
    out = []
    for name, start, dur in events:
        end = start + dur
        for w0, w1 in windows:
            lo, hi = max(start, w0), min(end, w1)
            if hi > lo:
                out.append((name, lo, hi - lo))
    return out


def named(events, prefixes):
    """Events whose name starts with one of ``prefixes``."""
    return [ev for ev in events if ev[0].startswith(prefixes)]


def top_names(events, k):
    """``[[name, seconds]]`` of the ``k`` names with most summed time."""
    total = {}
    for name, _start, dur in events:
        total[name] = total.get(name, 0.0) + dur
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:k]
    return [[name, dur * 1e-9] for name, dur in ranked]


def gaps(events, windows, k):
    """``[[label, seconds]]`` of the ``k`` longest stretches inside the
    windows in which no event ran, each labelled by the events that
    bracket it (``window start`` / ``window end`` at an edge)."""
    found = []
    for w0, w1 in windows:
        inside = sorted(clip(events, [(w0, w1)]), key=lambda ev: ev[1])
        edge, before = w0, "window start"
        for name, start, dur in inside:
            if start > edge:
                found.append((start - edge, f"{before} -> {name}"))
            if start + dur > edge:
                edge, before = start + dur, name
        if w1 > edge:
            found.append((w1 - edge, f"{before} -> window end"))
    found.sort(key=lambda g: -g[0])
    return [[label, dur * 1e-9] for dur, label in found[:k]]


# ------------------------------------------------------------- reduction

def device_lines(planes):
    """``{device index: (ops, modules)}`` of the TPU planes."""
    out = {}
    for name, lines in planes.items():
        m = DEVICE_PLANE.match(name)
        if m and lines.get(OP_LINE):
            out[int(m.group(1))] = (lines[OP_LINE],
                                    lines.get(MODULE_LINE, []))
    return out


def reduce_trace(planes, module_prefix):
    """What the per-layer readers and the last line need, in seconds.

    The timed windows of a device are its module-line events whose name
    starts with ``module_prefix`` (the jitted solve's own name): the
    other programs of an iteration (generate, check) and the host's gaps
    between programs lie outside them.
    """
    devices = {}
    for dev, (ops, modules) in sorted(device_lines(planes).items()):
        windows = union(spans(named(modules, (module_prefix,))))
        timed = clip(ops, windows)
        all_spans = spans(ops)
        devices[dev] = {
            "ops": ops, "windows": windows, "timed_ops": timed,
            "n_timed": len(named(modules, (module_prefix,))),
            "timed_s": length(windows) * 1e-9,
            "timed_busy_s": length(spans(timed)) * 1e-9,
            "busy_s": length(all_spans) * 1e-9,
            "window_s": (max(e for _s, e in all_spans)
                         - min(s for s, _e in all_spans)) * 1e-9,
        }
    if not devices:
        raise RuntimeError(
            "no TPU plane with an op line in the trace: planes "
            f"{sorted(planes)}")
    return {"devices": devices, "module_prefix": module_prefix}
