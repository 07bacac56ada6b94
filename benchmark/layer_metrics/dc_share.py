"""Share of the timed solves' device busy time in the tridiagonal divide
and conquer: every detail ``tridiag_eig/*`` (``leaf``, ``secular``,
``merge`` and the stage's ops outside a phase), mean over the devices
(``benchmark/eig_share.py``).  Its redistributions count as ``redist``."""
import eig_share

LAYER = "Spectral"
UNIT = "%"
MOVES = "solve_s"


def read(trace, run):
    return eig_share.read_stage(trace, run, "tridiag_eig")
