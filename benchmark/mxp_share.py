"""Seconds a solve under the phases of the mixed-precision solve
(``el.mixed_solve``), from the scope details of
``scopes.summary(trace, run)["seconds"]``: the refinement
(``refine/<phase>``: the ops ``el.mixed_solve/el.refine/k<step>/residual``
and ``.../correct`` name, and ``refine/-``, the loop itself and the
stopping test) and the unpivoted factor's trailing updates
(``lu_nopiv/update``), both the mean over the devices.  The readers of
the ``Mixed precision`` layer share it
(``layer_metrics/refine_share.py``, ``mxp_update_mxu_util.py``)."""
import scopes
from lstsq_share import busy_a_solve  # noqa: F401  (the readers' divisor)

OPERATOR = "mixed_solve"


def _seconds(trace, run):
    """``{detail: seconds a solve}`` in a cell that runs ``mixed_solve``;
    None anywhere else, and where the program names no scope."""
    if run["facts"].get("operator") != OPERATOR:
        return None
    result = scopes.summary(trace, run)
    return None if result is None else result["seconds"]


def refine_seconds(trace, run):
    """Seconds a solve under ``el.refine``; None as above, and where the
    program names no such scope."""
    seconds = _seconds(trace, run)
    if seconds is None:
        return None
    found = [s for detail, s in seconds.items()
             if detail.startswith("refine/")]
    return sum(found) if found else None


def update_seconds(trace, run):
    """Seconds a solve under ``lu_nopiv/update``; None as above."""
    seconds = _seconds(trace, run)
    return None if seconds is None else seconds.get("lu_nopiv/update")
