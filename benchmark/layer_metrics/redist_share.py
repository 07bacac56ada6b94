"""Share of the timed solves' device busy time under an ``el.redist.*`` scope:
all a public ``redistribute`` / ``panel_spread`` / ``row_permute`` entry
emits, the collectives AND the local pack / unpack / reshape / copy beside
them, mean over the devices (``benchmark/scopes.py``).  Op time on the
core's line, not exposed time.  Reported across chips only."""
import scopes

LAYER = "Redistribution"
UNIT = "%"
MOVES = "solve_s"


def read(trace, run):
    if run["facts"]["chips"] == 1:
        return None
    return scopes.share(trace, run, (scopes.REDIST,))
