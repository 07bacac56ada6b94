"""Share of the HBM roofline the tridiagonalization's matvecs reach: the
bytes a one-stage reduction must read for them (``facts.hemv_bytes``: the
stored triangle of the TRUE trailing matrix once a column,
``reference_eig.hemv_bytes``) over the ``hermitian_tridiag/hemv`` seconds
a solve over the chips' published HBM bandwidth (``peaks.json``).  The
phase is bandwidth-bound (4 flops a byte read at most), so this is its
roofline share, and no sound one-stage reduction reads over 100: a
program that reads the whole fixed trailing view twice a column reads far
under it."""
import eig_share

LAYER = "Spectral"
UNIT = "%"
MOVES = "solve_s"


def read(trace, run):
    seconds = eig_share.stage_seconds(trace, run, "hermitian_tridiag", "hemv")
    facts = run["facts"]
    bandwidth = run["peak"].get("hbm_bytes_per_s")
    if not seconds or not bandwidth or "hemv_bytes" not in facts:
        return None
    return 100.0 * facts["hemv_bytes"] / seconds / (bandwidth * facts["chips"])
