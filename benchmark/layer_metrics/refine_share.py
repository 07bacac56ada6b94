"""Share of the timed solves' device busy time under the refinement of
the mixed-precision solve (``el.mixed_solve/el.refine``: every residual
``B - A X``, A's norm, every correction's two triangular sweeps, the loop
and its stopping test), mean over the devices
(``benchmark/mxp_share.py``): the price of the lower precision, which a
float32 direct solve does not pay.  The first solve's sweeps are not in
it (``sweep_share`` reads them).  Read where the cell runs
``mixed_solve`` and the program names the scope."""
import mxp_share

LAYER = "Mixed precision"
UNIT = "%"
MOVES = "solve_s"


def read(trace, run):
    seconds = mxp_share.refine_seconds(trace, run)
    if seconds is None:
        return None
    return 100.0 * seconds / mxp_share.busy_a_solve(trace)
