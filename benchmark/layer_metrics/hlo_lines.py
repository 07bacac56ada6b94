"""Lines of the timed program's optimized HLO
(``compiled.as_text().count("\\n")``): the size of the unrolled blocked
loop, which sets the trace, lower and compile seconds of set-up."""
LAYER = "Drivers"
UNIT = "lines"
MOVES = "setup_s"


def read(trace, run):
    del trace
    return run["facts"]["hlo_lines"]
