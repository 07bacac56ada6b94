"""Share of the timed solves' device busy time under the ``update`` scope (the
trailing-update matmuls that do the flops; ``tail``, the replicated finish
of a distributed factorization, counts with it), mean over the devices
(``benchmark/scopes.py``).  Higher is better: time outside it computes
little."""
import scopes

LAYER = "BLAS"
UNIT = "%"
MOVES = "solve_s"


def read(trace, run):
    return scopes.share(trace, run, ("update", "tail"))
