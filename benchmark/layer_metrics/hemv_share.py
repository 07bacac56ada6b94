"""Share of the timed solves' device busy time under the ``hemv`` scope:
the one symmetric matvec a column of the Householder tridiagonalization
makes against the trailing matrix (``el.hermitian_tridiag/k<panel>/hemv``),
the phase of the Hermitian eigensolve that HBM bandwidth bounds, mean over
the devices (``benchmark/eig_share.py``).  Read where the cell runs
``herm_eig`` and the program names the scope."""
import eig_share

LAYER = "Spectral"
UNIT = "%"
MOVES = "solve_s"


def read(trace, run):
    return eig_share.read_stage(trace, run, "hermitian_tridiag", "hemv")
