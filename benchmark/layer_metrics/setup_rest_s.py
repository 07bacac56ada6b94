"""``setup_s`` less the seconds JAX traced, lowered and compiled or
loaded in (``benchmark/setup_parts.py``): the start of Python, JAX and
the TPU, imports, the warm generate-solve-check and its transfers.  Never
negative."""
import setup_parts

LAYER = "Entry points"
UNIT = "s"
MOVES = "setup_s"


def read(trace, run):
    del trace
    return setup_parts.rest_seconds(run)
