"""The yardstick's own operands, flop counts and residual check.

Plain ``jax.numpy``; imports nothing of ``elemental_tpu`` and takes
nothing the program has made.  The operand is a function of the GLOBAL
index and a 32-bit key alone, so the program's distributed fill and the
check's ordinary array are the same matrix on any grid; the check
regenerates A and B itself and is handed only the program's X.

The hash, the Gershgorin-HPD operand and the backward-error formula are
copies of ``chip_smoke.py``'s (``_hash_pm1``, ``gen_hpd``,
``gen_general``, ``backward_error``); the key is a traced argument here,
so one compiled program serves every seed and every iteration.
"""
import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def operand_key(seed: int, iteration: int, which: int) -> int:
    """32-bit key of operand ``which`` (0 = A, 1 = B) of one solve."""
    return (seed * 0xC2B2AE3D + iteration * 0x9E3779B1 + which * 0x85EBCA77
            + 0x27D4EB2F) & 0xFFFFFFFF


def hash_pm1(i, j, key):
    """uint32 mix of (i, j, key) -> float32 in [-1, 1)."""
    x = (i.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
         ^ j.astype(jnp.uint32) * jnp.uint32(0x85EBCA77)
         ^ key.astype(jnp.uint32))
    x = (x ^ (x >> 15)) * jnp.uint32(0x2C1B3C6D)
    x = (x ^ (x >> 12)) * jnp.uint32(0x297A2D39)
    x = x ^ (x >> 15)
    return (x >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -23) - 1.0


def entry_uniform_pm1(n, key):
    """f(i, j): entries uniform in [-1, 1)."""
    del n
    return lambda i, j: hash_pm1(i, j, key)


def entry_hpd_gershgorin(n, key):
    """f(i, j): symmetric, off-diagonal entries in [-1, 1), diagonal 2n:
    positive definite by Gershgorin."""
    def f(i, j):
        v = hash_pm1(jnp.minimum(i, j), jnp.maximum(i, j), key)
        return jnp.where(i == j, jnp.float32(2.0 * n), v)
    return f


def entry_hpd_shifted(n, key):
    """f(i, j): symmetric, off-diagonal entries uniform in [-1, 1),
    diagonal 2 sqrt(n).  The off-diagonal part is a Wigner matrix of
    entry variance 1/3, spectrum within +-1.155 sqrt(n), so A has its
    eigenvalues in about [0.85, 3.15] sqrt(n): positive definite with a
    margin of 0.85 sqrt(n) against edge fluctuations of order 1, and,
    unlike the Gershgorin operand, not dominated by its diagonal: the
    factor's off-diagonal work decides the answer's accuracy."""
    def f(i, j):
        v = hash_pm1(jnp.minimum(i, j), jnp.maximum(i, j), key)
        return jnp.where(i == j, jnp.float32(2.0 * n ** 0.5), v)
    return f


ENTRIES = {"uniform_pm1": entry_uniform_pm1,
           "hpd_gershgorin": entry_hpd_gershgorin,
           "hpd_shifted": entry_hpd_shifted}


#: rows of A the check holds at a time (0.5 GB of float32 at n = 32768)
BLOCK_ROWS = 4096


def plain_block(entry, row0, rows, cols, sharding=None):
    """Rows ``row0 .. row0 + rows`` of an operand as an ordinary array,
    from global iotas."""
    i = row0 + jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
    a = entry(i, j)
    if sharding is not None:
        a = jax.lax.with_sharding_constraint(a, sharding)
    return a


def residuals(entry_a, entry_b, n, nrhs, X, sharding=None):
    """The numbers ``correct`` is decided from, at HIGHEST, with A and B
    regenerated a block of rows at a time so that the check never holds
    more than a slice of A:

    * ``backward_error``: ||B - A X||_F / (||A||_F ||X||_F + ||B||_F)
    * ``hpl_scaled``: HPL's ||Ax-b||_inf / (eps (||A||_inf ||x||_inf +
      ||b||_inf) N), with the matrix inf-norm (largest row sum) for A and
      the largest entry for x and b, as HPL defines passing (< 16).
    """
    rows = min(BLOCK_ROWS, n)
    if n % rows:
        raise ValueError(f"n = {n} is not a multiple of {rows} rows")

    def block(b):
        A = plain_block(entry_a, b * rows, rows, n, sharding)
        B = plain_block(entry_b, b * rows, rows, nrhs)
        R = B - jnp.matmul(A, X, precision=HIGHEST)
        return (jnp.sum(R * R), jnp.sum(A * A), jnp.sum(B * B),
                jnp.max(jnp.abs(R)), jnp.max(jnp.sum(jnp.abs(A), axis=1)),
                jnp.max(jnp.abs(B)))

    r2, a2, b2, r_max, a_inf, b_max = jax.lax.map(
        block, jnp.arange(n // rows, dtype=jnp.int32))
    backward = jnp.sqrt(jnp.sum(r2)) / (
        jnp.sqrt(jnp.sum(a2)) * jnp.linalg.norm(X) + jnp.sqrt(jnp.sum(b2)))
    eps = jnp.finfo(X.dtype).eps
    hpl = jnp.max(r_max) / (eps * n * (
        jnp.max(a_inf) * jnp.max(jnp.abs(X)) + jnp.max(b_max)))
    return {"backward_error": backward, "hpl_scaled": hpl}


def solve_flops(factor: str, n: int, nrhs: int) -> float:
    """Flops the algorithm needs for one factor + two triangular sweeps,
    from its shapes: Cholesky n^3/3, LU 2n^3/3, each sweep n^2 nrhs."""
    factor_flops = {"cholesky": n ** 3 / 3.0, "lu": 2.0 * n ** 3 / 3.0}
    return factor_flops[factor] + 2.0 * float(n) ** 2 * nrhs
