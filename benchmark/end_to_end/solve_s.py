"""Median host-clock seconds of ONE call of the compiled public driver
(factor + both triangular sweeps), operands on the device, ending in
``block_until_ready`` on X; over every solve completed in the window."""
import statistics

UNIT = "s"


def read(run):
    return statistics.median(run["solve_seconds"])
