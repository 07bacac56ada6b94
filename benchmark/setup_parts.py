"""``setup_s`` split by the program's own compile log.

``elemental_tpu.obs.compile_log`` (in the process the benchmark shares
with the program) keeps, from ``jax.monitoring``, the SELF seconds of
every span in which JAX traced a function's Python (``trace``), lowered
it to MLIR (``lower``) or compiled it or read it back from the persistent
cache (``backend``), and the cache's requests, hits and misses.  Spans
nest (the trace of ``bench_solve`` holds the traces of every inner
``jit``), and self time is a span's duration less that of the spans
inside it, so the three sums are disjoint parts of the process's wall
time, and ``setup_s`` less the three is the rest of it: the start of
Python, JAX and the TPU, imports, and the warm generate-solve-check with
its transfers.  A run that compiled afresh builds its session twice
(``run.py:build_session``): ``setup_s`` holds both rounds and so do the
sums.

The readers of the five parts share this file
(``layer_metrics/setup_trace_s.py``, ``setup_lower_s.py``,
``setup_backend_s.py``, ``setup_cache_misses.py``, ``setup_rest_s.py``).
Nothing to read where the run states no ``setup_s``, where the program
has no compile log (a parent commit), and where the log holds no record.
"""


def log():
    """The program's compile log, or None where it has none."""
    try:
        from elemental_tpu.obs import compile_log
    except ImportError:
        return None
    return compile_log.LOG


def totals(run):
    """The log's totals, or None as above."""
    if "setup_s" not in run:
        return None
    found = log()
    if found is None:
        return None
    read = found.totals()
    return read if sum(read["records"].values()) else None


def stage_seconds(run, stage):
    """Self seconds of the spans of ``stage``, or None."""
    read = totals(run)
    return None if read is None else read["seconds"][stage]


def cache_misses(run):
    """Backend compiles that asked the persistent cache and compiled all
    the same, or None."""
    read = totals(run)
    return None if read is None else read["misses"]


def rest_seconds(run):
    """``setup_s`` less the three stages' seconds, never negative, or
    None."""
    read = totals(run)
    if read is None:
        return None
    return max(run["setup_s"] - sum(read["seconds"].values()), 0.0)
