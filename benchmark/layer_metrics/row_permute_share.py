"""Share of the timed solves' device busy time under ``el.redist.row_permute``
(``move_rows`` / ``permute_rows_storage``: LU's pivot swaps of every panel
step and the permutation of B), mean over the devices
(``benchmark/scopes.py``).  On a grid the swaps run through the engine and
cross ICI; ``scopes.classify`` books them as ``redist`` before it looks at
the phase, so ``swap_share`` cannot see them.  Read for the pivoted driver
across chips only."""
import detail_share

LAYER = "Redistribution"
UNIT = "%"
MOVES = "solve_s"


def read(trace, run):
    return detail_share.read_lu_on_a_grid(trace, run,
                                          "el.redist.row_permute")
