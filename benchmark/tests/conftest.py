"""The benchmark's own tests: run by hand, outside tier-1,

    python -m pytest benchmark/tests -q

on four virtual CPU devices.  They rehearse the harness and check the
yardstick's arithmetic; no number they produce is a measurement."""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_num_cpu_devices", 4)

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]
