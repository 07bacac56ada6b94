"""Share of the ONE-pass matmul roofline the trailing updates of the
mixed-precision factorization reach: their flops from their shapes
(``facts.mxp_update_flops``: ``sum_k 2 (n - e_k)^2 nb_k``, the least any
right-looking LU of that block size does, ``reference_mxp.update_flops``)
over the seconds a solve under ``lu_nopiv/update`` (mean over the devices,
``benchmark/mxp_share.py``) over the chips' published bf16 peak
(``peaks.json``).  The updates' operands are bfloat16 and one pass is all
the MXU does for them, so this is the phase's share of the only peak the
chip has, and no sound program reads over 100: the rounding of the panels
and the read and write of the float32 window count in the seconds.  Read
where the cell runs ``mixed_solve`` and the program names the scope."""
import mxp_share

LAYER = "Mixed precision"
UNIT = "%"
MOVES = "solve_s"


def read(trace, run):
    facts = run["facts"]
    seconds = mxp_share.update_seconds(trace, run)
    if not seconds or "mxp_update_flops" not in facts:
        return None
    peak = run["peak"]["bf16_flops_per_s"] * facts["chips"]
    return 100.0 * facts["mxp_update_flops"] / seconds / peak
