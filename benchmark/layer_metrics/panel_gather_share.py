"""Share of the timed solves' device busy time under
``el.redist.MC_MR.to.STAR_STAR``, mean over the devices
(``benchmark/scopes.py``): in the distributed pivoted LU that hop is the
panel gathered to every chip at every step (and the crossover tail's one
gather), collective and local unpack together.  Read for the pivoted
driver across chips only: the 2x2 Cholesky makes the same hop for its
diagonal blocks, where it is not a panel."""
import detail_share

LAYER = "Redistribution"
UNIT = "%"
MOVES = "solve_s"


def read(trace, run):
    return detail_share.read_lu_on_a_grid(trace, run,
                                          "el.redist.MC_MR.to.STAR_STAR")
