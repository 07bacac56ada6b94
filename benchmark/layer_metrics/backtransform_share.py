"""Share of the timed solves' device busy time in the back-transformation
of the eigenvectors: every detail ``apply_q_herm_tridiag/*`` (the blocked
reflector products of ``k<panel>/apply``), mean over the devices
(``benchmark/eig_share.py``)."""
import eig_share

LAYER = "Spectral"
UNIT = "%"
MOVES = "solve_s"


def read(trace, run):
    return eig_share.read_stage(trace, run, "apply_q_herm_tridiag")
