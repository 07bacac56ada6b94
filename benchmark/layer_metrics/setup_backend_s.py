"""Seconds of set-up in the backend: XLA:TPU compiling a program, or the
persistent cache handing its executable back; SELF time of the program's
``backend`` spans (``benchmark/setup_parts.py``)."""
import setup_parts

LAYER = "Device"
UNIT = "s"
MOVES = "setup_s"


def read(trace, run):
    del trace
    return setup_parts.stage_seconds(run, "backend")
