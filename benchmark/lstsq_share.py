"""Share of the timed solves' device busy time under ONE phase of the
tall-skinny least-squares route: the scope detail ``tsqr/<phase>`` of
``scopes.summary(trace, run)["seconds"]`` (the ops
``el.least_squares/el.tsqr/k00/<phase>`` names) over the busy seconds a
solve, both the mean over the devices.  The readers of the route share
it (``layer_metrics/tsqr_local_share.py``, ``tsqr_tree_share.py``,
``lstsq_hbm_util.py``)."""
import scopes


def busy_a_solve(trace):
    """Busy seconds a solve, mean over the devices."""
    devices = trace["devices"].values()
    return sum(d["timed_busy_s"] / d["n_timed"]
               for d in devices) / len(devices)


def phase_seconds(trace, run, phase):
    """Seconds a solve under ``tsqr/<phase>`` in a cell that runs
    ``least_squares``; None anywhere else, and where the program names no
    such scope (a program without the route)."""
    if run["facts"].get("operator") != "least_squares":
        return None
    result = scopes.summary(trace, run)
    if result is None:
        return None
    return result["seconds"].get(f"tsqr/{phase}")


def read_phase(trace, run, phase):
    """The phase's share (%) of the busy time, or None as above."""
    seconds = phase_seconds(trace, run, phase)
    if seconds is None:
        return None
    return 100.0 * seconds / busy_a_solve(trace)
