"""Share of the timed solves' device busy time in the QR-based steps of
the polar iteration: every op under ``el.polar/qdwh_qr<steps>`` (the
blocked ``qr`` of the (2n x n) stack, its thin Q by ``apply_q`` on an
identity, ``Q1 Q2^T``), mean over the devices (``benchmark/svd_share.py``;
the ``{"svd_stages"}`` line keeps the steps apart).  Read where the cell
runs ``svd`` and the program names the scopes."""
import svd_share

LAYER = "Polar SVD"
UNIT = "%"
MOVES = "solve_s"


def read(trace, run):
    return svd_share.read_share(trace, run, ("qdwh_qr",))
