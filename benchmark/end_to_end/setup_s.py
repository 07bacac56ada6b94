"""Process start to the first timed solve: JAX and TPU start-up, tracing
and lowering, compile or cache load, one warm generate-solve-check."""
UNIT = "s"


def read(run):
    return run["setup_s"]
