"""Share of the timed solves' device busy time in the polar stage of the
dense SVD: every op under ``el.polar`` (the QDWH iteration, the scale and
``H = U_p^T A``) and under ``svd_u`` (``U = U_p V``), mean over the devices
(``benchmark/svd_share.py``).  What this route adds to the Hermitian
eigensolve it ends in; ``svd_eig_share`` reads the rest.  Read where the
cell runs ``svd`` and the program names the scopes."""
import svd_share

LAYER = "Polar SVD"
UNIT = "%"
MOVES = "solve_s"


def read(trace, run):
    return svd_share.read_share(trace, run, svd_share.POLAR)
