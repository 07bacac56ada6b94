"""Share of the timed solves' device busy time in the exchanges of the
back-transform: every op under an ``el.redist.`` name whose path lies under
``el.apply_q_herm_tridiag`` (a panel's ``[STAR,STAR]`` gather and the
reflectors' ``[MC,STAR]`` hop), mean over the devices
(``benchmark/eig_wire.py``).  ``backtransform_share`` reads the stage's
matmuls beside it.  Read where the cell runs ``herm_eig`` across chips."""
import eig_wire

LAYER = "Spectral"
UNIT = "%"
MOVES = "solve_s"


def read(trace, run):
    return eig_wire.read_share(trace, run, "apply_q_herm_tridiag")
