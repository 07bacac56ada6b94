"""Mixed-precision solve, HPL-MxP's shape: an LU factorization WITHOUT
pivoting whose trailing updates run in one bfloat16 pass, refined on the
device to the float32 limit (``el.mixed_solve``).  The operand must not
need pivoting: here entries in [-1, 1) beside a diagonal of 2 sqrt(n)."""
import jax
import numpy as np
from _common import setup, report

el, args, grid = setup()
n = args.input("--n", "matrix size", 384)
nb = args.input("--nb", "panel width", 128)
args.process(report=True)

rng = np.random.default_rng(0)
F = rng.uniform(-1, 1, size=(n, n)).astype(np.float32)
np.fill_diagonal(F, 2 * np.sqrt(n))
b = rng.uniform(-1, 1, size=(n, 1)).astype(np.float32)
A = el.from_global(F, el.MC, el.MR, grid=grid)
B = el.from_global(b, el.MC, el.MR, grid=grid)


def backward_error(X):
    x = np.asarray(el.to_global(X), np.float64)
    return float(np.linalg.norm(b - F.astype(np.float64) @ x) / (
        np.linalg.norm(F) * np.linalg.norm(x) + np.linalg.norm(b)))


# one program: factor, first solve, residual, correction, stopping test
X, info = jax.jit(lambda A, B: el.mixed_solve(A, B, nb=nb))(A, B)
X0, _ = jax.jit(lambda A, B: el.mixed_solve(A, B, nb=nb, max_steps=0))(A, B)
report("mixed_solve", n=n, backward_error=backward_error(X),
       unrefined=backward_error(X0), steps=int(info["steps"]),
       converged=bool(info["converged"]))
