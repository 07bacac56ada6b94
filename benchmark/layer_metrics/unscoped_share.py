"""Share of the timed solves' device busy time in ops that carry no ``el.``
scope: copies and layout changes the compiler made, and anything the
program failed to name, mean over the devices (``benchmark/scopes.py``)."""
import scopes

LAYER = "Device"
UNIT = "%"
MOVES = "solve_s"


def read(trace, run):
    return scopes.share(trace, run, (scopes.UNSCOPED,))
