"""Share of the timed solves' device busy time the Hermitian eigensolve's
reduction spends exchanging INSIDE its column loop: the ops under an
``el.redist.`` name whose path lies under ``el.hermitian_tridiag`` and holds
``while/body`` (the matvec's vector to ``[MR,STAR]`` and its result
replicated again, one set of small dependent collectives a column, with the
pack / unpack beside them: the part latency bounds), mean over the devices
(``benchmark/eig_wire.py``).  The compiler's own join of the product's
partial sums carries the product's name and reads ``hemv``.  Read where the
cell runs ``herm_eig`` across chips."""
import eig_wire

LAYER = "Spectral"
UNIT = "%"
MOVES = "solve_s"


def read(trace, run):
    return eig_wire.read_share(trace, run, eig_wire.COLUMN)
