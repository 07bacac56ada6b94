"""Share of the timed solves' device busy time under the ``tree`` scope
of the tall-skinny least-squares route
(``el.least_squares/el.tsqr/k00/tree``): the one all-gather of the chips'
R factors and the QR of their stack, the same on every chip -- p n^2
numbers, so latency and not bandwidth -- mean over the devices
(``benchmark/lstsq_share.py``).  Read where the cell runs
``least_squares`` and the program names the scope."""
import lstsq_share

LAYER = "Least squares"
UNIT = "%"
MOVES = "solve_s"


def read(trace, run):
    return lstsq_share.read_phase(trace, run, "tree")
