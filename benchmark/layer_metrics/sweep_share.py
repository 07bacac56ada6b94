"""Share of the timed solves' device busy time under a public solve's
``sweeps`` scope (the two triangular sweeps of ``blas/level3.trsm``, their
local work; their redistributions count as ``redist``), mean over the
devices (``benchmark/scopes.py``)."""
import scopes

LAYER = "BLAS"
UNIT = "%"
MOVES = "solve_s"


def read(trace, run):
    return scopes.share(trace, run, (scopes.SWEEP,))
