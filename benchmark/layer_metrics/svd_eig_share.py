"""Share of the timed solves' device busy time in the Hermitian
eigensolve of the polar factor H inside the dense SVD: every op under the
inner ``el.herm_eig`` (``heig.1x1.b2b``'s program at the same order, on a
positive semi-definite operand), mean over the devices
(``benchmark/svd_share.py``).  The share of this cell that code shared
with the eigensolve's cells moves.  Read where the cell runs ``svd`` and
the program names the scopes."""
import svd_share

LAYER = "Spectral"
UNIT = "%"
MOVES = "solve_s"


def read(trace, run):
    return svd_share.read_share(trace, run, ("eig",))
