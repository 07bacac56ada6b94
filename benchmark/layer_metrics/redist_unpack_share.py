"""Share of the timed solves' device busy time in the ``unpack`` part of the
``el.redist.*`` scopes: the local ops AFTER an exchange's collective: the
interleave, the cyclic filter, the slice to the true extent, the mask, the
decode; an exchange with no collective is all of it.  The ops whose
``op_name`` holds ``unpack`` as the first part after the first ``el.redist.``
segment, mean over the devices (``benchmark/redist_parts.py``).  With the two
other parts and ``planned`` (no part named: the compiler's motion) it sums to
``redist_share``.  Reported across chips, where the program names the parts."""
import redist_parts

LAYER = "Redistribution"
UNIT = "%"
MOVES = "solve_s"


def read(trace, run):
    return redist_parts.read_share(trace, run, "unpack")
