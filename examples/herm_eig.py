"""Hermitian eigensolver (upstream ``examples/lapack_like/HermitianEig.cpp``).

``el.herm_eig`` is one traceable program: under ``jax.jit`` (as below, A
donated) the Householder tridiagonalization, the tridiagonal solve and the
back-transform compile together and run with no host round trip, for the
full spectrum and for an ``('index', il, iu)`` subset; only a
``('value', lo, hi)`` subset reads ``w`` on the host and must be called
eagerly.  In a device trace the three stages show by their scopes:
``el.herm_eig/el.hermitian_tridiag/k<panel>/{hemv,panel,update}``,
``el.tridiag_eig/k<level>/{leaf,secular,merge}`` (above n = 512) and
``el.apply_q_herm_tridiag/k<panel>/apply``.
"""
import jax
import numpy as np
from _common import setup, report

el, args, grid = setup()
n = args.input("--n", "matrix size", 200)
args.process(report=True)

rng = np.random.default_rng(0)
G = rng.normal(size=(n, n))
F = (G + G.T) / 2
A = el.from_global(F, el.MC, el.MR, grid=grid)
w, Z = jax.jit(el.herm_eig, donate_argnums=0)(A)
Zg = np.asarray(el.to_global(Z))
w = np.asarray(w)
resid = np.linalg.norm(F @ Zg - Zg * w[None, :]) / np.linalg.norm(F)
orth = np.linalg.norm(Zg.T @ Zg - np.eye(n))
report("herm_eig", n=n, resid=resid, orth=orth,
       w_min=float(w[0]), w_max=float(w[-1]))
