"""Share of the timed solves' device busy time under ONE scope detail
(an ``el.redist.*`` name or a ``<driver>/<phase>``) of
``scopes.summary(trace, run)["seconds"]``: the seconds a solve under it
over the busy seconds a solve, both the mean over the devices.  The
readers of the distributed pivoted LU share it
(``layer_metrics/row_permute_share.py``, ``panel_gather_share.py``)."""
import scopes


def read_lu_on_a_grid(trace, run, detail):
    """The share (%) in a cell that runs the pivoted driver across chips;
    None anywhere else, and where the program names no such scope."""
    facts = run["facts"]
    if facts.get("operator") != "lu_solve" or facts["chips"] == 1:
        return None
    result = scopes.summary(trace, run)
    if result is None or detail not in result["seconds"]:
        return None
    devices = trace["devices"].values()
    busy_a_solve = sum(d["timed_busy_s"] / d["n_timed"]
                       for d in devices) / len(devices)
    return 100.0 * result["seconds"][detail] / busy_a_solve
