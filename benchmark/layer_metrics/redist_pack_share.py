"""Share of the timed solves' device busy time in the ``pack`` part of the
``el.redist.*`` scopes: the local ops that FEED an explicit collective of an
exchange: the pad, the reshape into per-peer blocks, the cast or encode to the
wire dtype.  The ops whose ``op_name`` holds ``pack`` as the first part after
the first ``el.redist.`` segment, mean over the devices
(``benchmark/redist_parts.py``).  With the two other parts and ``planned`` (no
part named: the compiler's motion) it sums to ``redist_share``.  Reported
across chips, where the program names the parts."""
import redist_parts

LAYER = "Redistribution"
UNIT = "%"
MOVES = "solve_s"


def read(trace, run):
    return redist_parts.read_share(trace, run, "pack")
