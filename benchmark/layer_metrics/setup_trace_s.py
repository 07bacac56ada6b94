"""Seconds of set-up in which JAX traced a function's Python: SELF time
of the program's ``trace`` spans (``benchmark/setup_parts.py``), the
drivers' unrolled loops among them."""
import setup_parts

LAYER = "Drivers"
UNIT = "s"
MOVES = "setup_s"


def read(trace, run):
    del trace
    return setup_parts.stage_seconds(run, "trace")
