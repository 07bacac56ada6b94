"""Share of the timed solves' device busy time under the phases of ONE
stage of the Hermitian eigensolve: every scope detail
``<stage>/<phase>`` of ``scopes.summary(trace, run)["seconds"]`` (the
stage's own ops outside a phase, ``<stage>/-``, among them) over the busy
seconds a solve, both the mean over the devices.  The readers of the
eigensolve's stages share it (``layer_metrics/hemv_share.py``,
``dc_share.py``, ``backtransform_share.py``, ``hemv_hbm_util.py``)."""
import scopes


def stage_seconds(trace, run, stage, phase=None):
    """Seconds a solve under ``<stage>/<phase>`` (every phase of the stage
    where ``phase`` is None) in a cell that runs ``herm_eig``; None
    anywhere else, and where the program names no such scope."""
    if run["facts"].get("operator") != "herm_eig":
        return None
    result = scopes.summary(trace, run)
    if result is None:
        return None
    found = [s for detail, s in result["seconds"].items()
             if detail.startswith(stage + "/")
             and phase in (None, detail.split("/", 1)[1])]
    return sum(found) if found else None


def read_stage(trace, run, stage, phase=None):
    """The stage's (or its one phase's) share (%) of the busy time, or None
    as above."""
    seconds = stage_seconds(trace, run, stage, phase)
    if seconds is None:
        return None
    devices = trace["devices"].values()
    busy_a_solve = sum(d["timed_busy_s"] / d["n_timed"]
                       for d in devices) / len(devices)
    return 100.0 * seconds / busy_a_solve
