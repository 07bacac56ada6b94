"""Share of the timed solves' device windows in which no op ran, on the
device that idles most: 1 - (union of op intervals inside the windows) /
(length of the windows), from the xplane."""
LAYER = "Device"
UNIT = "%"
MOVES = "solve_s"


def read(trace, run):
    del run
    return max(100.0 * (1.0 - d["timed_busy_s"] / d["timed_s"])
               for d in trace["devices"].values())
