"""Share of the published matmul peak the polar stage of the dense SVD
reaches: its flops from the shapes and the schedule's step kinds
(``facts.polar_flops``, ``reference_svd.polar_flops``: the plain QR-based
and Cholesky-based steps and the two outer products, whatever implements
them) over the seconds a solve under ``el.polar`` and ``svd_u`` (what
``polar_share`` reads, mean over the devices, ``benchmark/svd_share.py``)
over the chips' published bf16 peak (``peaks.json``: 197 TFLOP/s).  The
stage is matmul-bound by its flops, so this is its roofline share; every
product is float32 at HIGHEST, six bf16 passes, which caps it near
16.7 %: a reading over that means the flops are counted too high or the
seconds leave out part of the stage.  No new kernel stands behind it."""
import svd_share

LAYER = "Polar SVD"
UNIT = "%"
MOVES = "solve_s"


def read(trace, run):
    facts = run["facts"]
    seconds = svd_share.seconds(trace, run, svd_share.POLAR)
    if not seconds or "polar_flops" not in facts:
        return None
    peak = run["peak"]["bf16_flops_per_s"] * facts["chips"]
    return 100.0 * facts["polar_flops"] / seconds / peak
