"""Seconds of set-up in which JAX lowered a traced program to MLIR: SELF
time of the program's ``lower`` spans (``benchmark/setup_parts.py``)."""
import setup_parts

LAYER = "Drivers"
UNIT = "s"
MOVES = "setup_s"


def read(trace, run):
    del trace
    return setup_parts.stage_seconds(run, "lower")
