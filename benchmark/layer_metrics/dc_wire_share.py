"""Share of the timed solves' device busy time in the exchanges of the
tridiagonal divide and conquer: every op under an ``el.redist.`` name whose
path lies under ``el.tridiag_eig`` (the hand-off of the replicated levels'
blocks to the ``[MC,MR]`` eigenvector matrix, the distributed merges'
gathers and SUMMA hops), mean over the devices (``benchmark/eig_wire.py``).
``dc_share`` reads the stage's own ops beside it.  Read where the cell runs
``herm_eig`` across chips."""
import eig_wire

LAYER = "Spectral"
UNIT = "%"
MOVES = "solve_s"


def read(trace, run):
    return eig_wire.read_share(trace, run, "tridiag_eig")
