"""Share of the timed solves' device busy time under the ``swap`` scope (LU's
row interchanges), mean over the devices (``benchmark/scopes.py``).  Only
the pivoted driver has the phase: nothing to read elsewhere."""
import scopes

LAYER = "Panels"
UNIT = "%"
MOVES = "solve_s"


def read(trace, run):
    if run["facts"].get("operator") != "lu_solve":
        return None
    return scopes.share(trace, run, ("swap",))
