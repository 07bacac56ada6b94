"""Tall-skinny least squares (upstream ``qr::TS`` / ``El::LeastSquares``):
millions of observations, a few features.  On a grid of several chips,
where every chip's share of the rows is at least 8192 times the number of
columns (and there are at most 256 columns), ``least_squares`` sends the
rows to the chips once, each chip factors its own, and only the small R
factors meet; any other shape, and any problem on one chip, takes the
blocked QR (``tall=False`` below)."""
import jax
import numpy as np
from _common import setup, report

el, args, grid = setup()
m = args.input("--m", "rows (observations)", 1048576)
n = args.input("--n", "columns (features)", 8)
args.process(report=True)

rng = np.random.default_rng(0)
F = rng.normal(size=(m, n))                       # features
b = F @ rng.normal(size=(n, 1)) + 0.1 * rng.normal(size=(m, 1))
A = el.from_global(F, el.MC, el.MR, grid=grid)
B = el.from_global(b, el.MC, el.MR, grid=grid)
with el.obs.metrics_scope() as counters:
    X = jax.jit(el.least_squares)(A, B)
xref, *_ = np.linalg.lstsq(F, b, rcond=None)
err = np.linalg.norm(np.asarray(el.to_global(X)) - xref) / np.linalg.norm(xref)
report("tall_lstsq", m=m, n=n, lstsq_err=err,
       tall=bool(counters.counter_value("lstsq_route", kind="tall")))
