"""End-to-end utilization of the published bf16 peak: the algorithm's
flops for one solve (``reference.solve_flops``, from its shapes) over the
device busy time of one solve (mean over the chips and the traced
solves) over chips x peak.  Six-pass float32 caps it near 1/6; it is a
share of the only peak the chip has, so no sound change reads over 100."""
LAYER = "BLAS"
UNIT = "%"
MOVES = "solve_s"


def read(trace, run):
    devs = trace["devices"].values()
    busy = sum(d["timed_busy_s"] / d["n_timed"] for d in devs) / len(devs)
    peak = run["peak"]["bf16_flops_per_s"] * run["facts"]["chips"]
    return 100.0 * run["facts"]["flops_per_solve"] / busy / peak
