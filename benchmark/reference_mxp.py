"""The yardstick's operand, flop counts and plain implementation of a
mixed-precision dense solve (HPL-MxP's shape): A x = b from an LU
factorization WITHOUT pivoting whose updates run in low precision,
refined to the accuracy of the high-precision solve.

Plain ``jax.numpy``; imports ``reference`` (the hash: an operand is a
function of the global index and a 32-bit key) and nothing of
``elemental_tpu``.  The check of a cell's answer is ``reference.residuals``
as it stands, on this file's entry for A and ``reference.entry_uniform_pm1``
for b.

The operand keeps HPL-MxP's PROPERTY (LU without pivoting is stable) and
not its generator.  The generator makes A diagonally dominant by rows
(diagonal = the row's absolute sum, about n / 4 beside entries under
0.5), and a matrix that is all diagonal hides what the trailing updates
compute: on such an operand the unrefined one-pass answer already reads
1.6e-8 and four steps of Jacobi, with no factorization at all, 8.8e-9
(float64 numpy with bf16-rounded update operands, n = 2048; ISSUE 45's
count), so no limit could tell a refined answer from an unrefined one.
``entry_shifted_pm1`` is the non-symmetric sibling of
``reference.entry_hpd_shifted``:

    a(i, j) = h(i, j) uniform in [-1, 1),   a(i, i) = 2 sqrt(n)

Its symmetric part is a Wigner matrix of entry variance 1/6 (spectrum
within +-0.82 sqrt(n)) shifted by 2 sqrt(n): positive definite with a
margin of about 1.18 sqrt(n), so every leading principal minor is
non-singular, the pivots of an unpivoted elimination stay of order
sqrt(n) and the growth is 1.006 at n = 2048; condition 2.3 there.  On it
the unrefined answer reads 2.1e-6, one refinement step 6.1e-10, a float32
direct solve 1.3e-9 (the same count).
"""
import jax
import jax.numpy as jnp

import reference

HIGHEST = reference.HIGHEST


def entry_shifted_pm1(n, key):
    """f(i, j): entries uniform in [-1, 1), diagonal 2 sqrt(n); not
    symmetric."""
    def f(i, j):
        v = reference.hash_pm1(i, j, key)
        return jnp.where(i == j, jnp.float32(2.0 * n ** 0.5), v)
    return f


def mxp_flops(n: int) -> float:
    """HPL-MxP's (and HPL's) count for one solve: 2/3 n^3 + 3/2 n^2,
    whatever the refinement does."""
    return 2.0 * float(n) ** 3 / 3.0 + 1.5 * float(n) ** 2


def update_flops(n: int, nb: int) -> float:
    """Flops of the trailing updates of a right-looking blocked LU with
    panels of ``nb`` columns, from its shapes: step k, whose panel ends at
    column e_k, does ``A22 -= L21 U12`` on the (n - e_k)^2 window with an
    inner dimension of nb_k: ``sum_k 2 (n - e_k)^2 nb_k``.  The least any
    right-looking LU of that block size does in its updates (a schedule
    that computes more than the window is not credited for it)."""
    total = 0.0
    for s in range(0, n, nb):
        e = min(s + nb, n)
        total += 2.0 * float(n - e) ** 2 * (e - s)
    return total


# ------------------------------------------------- the plain implementation

def plain_lu_nopiv(A, low=jnp.bfloat16):
    """Unblocked unpivoted LU, packed (unit-lower L below the diagonal, U
    on and above it): column j's multipliers in float32, then the rank-1
    update of the trailing matrix with BOTH operands rounded to ``low``
    and the product accumulated in float32 (``low=None``: float32
    operands).  Every update of the elimination runs in the low precision
    here, where a blocked program keeps the inside of a panel high: the
    same semantics at the coarsest rounding."""
    n = A.shape[0]
    idx = jnp.arange(n)

    def rounded(x):
        return x if low is None else x.astype(low).astype(jnp.float32)

    def body(j, a):
        col = a[:, j]
        l = jnp.where(idx > j, col / a[j, j], 0.0)
        u = jnp.where(idx > j, a[j], 0.0)
        a = a - jnp.outer(rounded(l), rounded(u))
        return a.at[:, j].set(jnp.where(idx > j, l, col))

    return jax.lax.fori_loop(0, n, body, A.astype(jnp.float32))


def plain_solve_after(LU, B):
    """``U^-1 L^-1 B`` from the packed factor, float32."""
    Y = jax.scipy.linalg.solve_triangular(LU, B, lower=True,
                                          unit_diagonal=True)
    return jax.scipy.linalg.solve_triangular(LU, Y, lower=False)


def plain_mixed_solve(A, B, steps, low=jnp.bfloat16):
    """The plain mixed-precision solve: :func:`plain_lu_nopiv`, the first
    solve, then ``steps`` rounds of ``r = b - A x`` (float32 at HIGHEST),
    ``d = U^-1 L^-1 r``, ``x += d``.  ``steps=0`` is the unrefined
    answer."""
    LU = plain_lu_nopiv(A, low)
    X = plain_solve_after(LU, B)
    for _ in range(steps):
        R = B - jnp.matmul(A, X, precision=HIGHEST)
        X = X + plain_solve_after(LU, R)
    return X
