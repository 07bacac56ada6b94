"""Share of the timed solves' device busy time under the ``local`` scope
of the tall-skinny least-squares route
(``el.least_squares/el.tsqr/k00/local``): each chip's Householder QR of
the rows it holds, the phase that reads and rewrites the chip's whole
share of A, mean over the devices (``benchmark/lstsq_share.py``).  Read
where the cell runs ``least_squares`` and the program names the scope."""
import lstsq_share

LAYER = "Least squares"
UNIT = "%"
MOVES = "solve_s"


def read(trace, run):
    return lstsq_share.read_phase(trace, run, "local")
