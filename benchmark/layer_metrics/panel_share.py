"""Share of the timed solves' device busy time the program spends under its
``diag`` and ``panel`` scopes (diagonal-block and panel factorizations, the
panel solve), mean over the devices: ``benchmark/scopes.py`` on the op
names of the compiled solve.  Nothing to read from a program that names
no scope."""
import scopes

LAYER = "Panels"
UNIT = "%"
MOVES = "solve_s"


def read(trace, run):
    return scopes.share(trace, run, ("diag", "panel"))
