"""Share of the timed solves' device windows covered by collective ops
(all-gather, all-to-all, all-reduce, collective-permute, reduce-scatter,
with -start/-done), on the device where it is largest.  Op time, not
exposed time.  Nothing to read on one chip."""
import xplane

LAYER = "Redistribution"
UNIT = "%"
MOVES = "solve_s"


def read(trace, run):
    if run["facts"]["chips"] == 1:
        return None
    return max(
        100.0 * xplane.length(xplane.spans(xplane.named(
            d["timed_ops"], xplane.COLLECTIVE_PREFIXES))) * 1e-9
        / d["timed_s"] for d in trace["devices"].values())
