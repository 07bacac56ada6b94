"""Rate at which the exchanges' LOCAL ops write: the GB the ``pack`` and
``unpack`` ops of the ``el.redist.*`` scopes write a solve (the logical
size of each op's result in the optimized HLO, once an event) over their
seconds, mean over the devices (``benchmark/redist_parts.py``).  Not a
roofline share: a copy at the HBM roofline writes at most half the
published bandwidth (``peaks.json``), whole passes over a shard read in
the hundreds, a padded relayout in single digits.  Reported across chips,
where the program names the parts."""
import redist_parts

LAYER = "Redistribution"
UNIT = "GB/s"
MOVES = "solve_s"


def read(trace, run):
    result = redist_parts.summary(trace, run)
    return None if result is None else result["relayout_gbps"]
