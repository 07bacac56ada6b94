"""Bytes per device the compiler plans for the timed program:
``argument + output + temp - alias`` of ``compiled.memory_analysis()``,
over 1e9.  Read by the benchmark from the executable it times."""
UNIT = "GB"


def read(run):
    return run["facts"]["plan_bytes"] / 1e9
