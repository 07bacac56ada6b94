"""Programs that set-up asked the persistent compile cache for and had to
compile all the same (``compile_cache_misses`` of the program's compile
log, ``benchmark/setup_parts.py``): 0 on a warm run."""
import setup_parts

LAYER = "Device"
UNIT = "programs"
MOVES = "setup_s"


def read(trace, run):
    del trace
    return setup_parts.cache_misses(run)
