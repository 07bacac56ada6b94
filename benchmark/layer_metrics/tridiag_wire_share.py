"""Share of the timed solves' device busy time in ALL the exchanges of the
Hermitian eigensolve's reduction to tridiagonal form: every op under an
``el.redist.`` name whose path lies under ``el.hermitian_tridiag`` (the
column loop's, which ``column_wire_share`` reads alone, the mirror of the
trailing view once a panel, the panel's ``[STAR,STAR]`` gather and
write-back, the update's ``[MC,STAR]`` / ``[STAR,MR]`` hops), mean over the
devices (``benchmark/eig_wire.py``).  Read where the cell runs ``herm_eig``
across chips."""
import eig_wire

LAYER = "Spectral"
UNIT = "%"
MOVES = "solve_s"


def read(trace, run):
    return eig_wire.read_share(trace, run, "hermitian_tridiag")
