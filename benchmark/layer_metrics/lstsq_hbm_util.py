"""Share of the HBM roofline a least-squares solve reaches: the bytes no
method can avoid (``facts.lstsq_bytes``: one read of A and of B, all
chips' shards together, ``reference_lstsq.lstsq_bytes``) over the busy
seconds of a solve (mean over the devices) over the chips' published HBM
bandwidth (``peaks.json``).  A solve is one pass over the operand at best
(4 n flops a byte at HIGHEST's six passes stay under the MXU's share at
n = 256), so this is its roofline share, and no sound solve reads over
100: one that moves the operand between chips, transposes it and passes
over it once a column reads far under it.  Read where the cell runs
``least_squares``."""
import lstsq_share

LAYER = "Least squares"
UNIT = "%"
MOVES = "solve_s"


def read(trace, run):
    facts = run["facts"]
    bandwidth = run["peak"].get("hbm_bytes_per_s")
    if (facts.get("operator") != "least_squares" or not bandwidth
            or "lstsq_bytes" not in facts):
        return None
    return 100.0 * facts["lstsq_bytes"] / lstsq_share.busy_a_solve(trace) \
        / (bandwidth * facts["chips"])
