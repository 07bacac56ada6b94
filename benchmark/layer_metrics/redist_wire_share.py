"""Share of the timed solves' device busy time in the ``wire`` part of the
``el.redist.*`` scopes: the EXPLICIT collectives of the exchanges themselves
(all-gather, all-to-all, collective-permute, all-reduce, reduce-scatter; an
async pair's ``-start`` and ``-done`` both): SELF time on the core's op line,
what the core waited, not time in flight.  The ops whose ``op_name`` holds
``wire`` as the first part after the first ``el.redist.`` segment, mean over
the devices (``benchmark/redist_parts.py``).  With the two other parts and
``planned`` (no part named: the compiler's motion) it sums to
``redist_share``.  Reported across chips, where the program names the parts."""
import redist_parts

LAYER = "Redistribution"
UNIT = "%"
MOVES = "solve_s"


def read(trace, run):
    return redist_parts.read_share(trace, run, "wire")
