"""The yardstick's check, flop count and byte count of a Hermitian
eigensolve: the full spectrum with vectors.

Plain ``jax.numpy`` at HIGHEST; imports ``reference`` (the operands: a
function of the global index and a 32-bit key) and nothing of
``elemental_tpu``, and takes nothing the program has made but its answer
``(w, Z)``.  A is regenerated here a block of rows at a time.

Why these three numbers are the whole check.  n orthonormal vectors with
a small residual ARE the spectrum: if ``Z^T Z = I`` and
``A Z = Z diag(w)`` to the limits, then ``A = Z diag(w) Z^T`` to the same
order, so every eigenvalue of A is one of the ``w`` (Weyl) and none is
missing or doubled.  No float64 oracle of the full size is needed, and
none would fit the run.  A subset, a wrong pairing of values and vectors,
a scaled or repeated vector, an unsorted answer each break one of them.
"""
import jax
import jax.numpy as jnp

import reference

HIGHEST = reference.HIGHEST


def residuals_eig(entry, n, w, Z, sharding=None):
    """The numbers ``correct`` is decided from, for the answer ``(w, Z)``
    (``w`` the n eigenvalues, ``Z`` the n x n eigenvectors as an ordinary
    array) of the operand ``entry``:

    * ``residual``: ||A Z - Z diag(w)||_F / (||A||_F ||Z||_F)
    * ``orthogonality``: ||Z^T Z - I||_F / sqrt(n)
    * ``descents``: how many i have w[i+1] < w[i] (ascending: 0)

    A is regenerated, and Z^T Z formed, ``reference.BLOCK_ROWS`` rows at
    a time, so the check holds a slice of each beside Z.
    """
    rows = min(reference.BLOCK_ROWS, n)
    if n % rows:
        raise ValueError(f"n = {n} is not a multiple of {rows} rows")
    if Z.shape != (n, n) or w.shape != (n,):
        raise ValueError(f"the full spectrum of order {n} is w {(n,)} and "
                         f"Z {(n, n)}; got {w.shape} and {Z.shape}")

    def block(b):
        A = reference.plain_block(entry, b * rows, rows, n, sharding)
        Zb = jax.lax.dynamic_slice_in_dim(Z, b * rows, rows, axis=0)
        R = jnp.matmul(A, Z, precision=HIGHEST) - Zb * w[None, :]
        # rows b of Z^T Z - I: (columns b of Z)^T Z
        Zc = jax.lax.dynamic_slice_in_dim(Z, b * rows, rows, axis=1)
        G = jnp.matmul(Zc.T, Z, precision=HIGHEST)
        i = b * rows + jax.lax.broadcasted_iota(jnp.int32, (rows, n), 0)
        j = jax.lax.broadcasted_iota(jnp.int32, (rows, n), 1)
        G = G - (i == j).astype(G.dtype)
        return jnp.sum(R * R), jnp.sum(A * A), jnp.sum(G * G)

    r2, a2, g2 = jax.lax.map(block, jnp.arange(n // rows, dtype=jnp.int32))
    residual = jnp.sqrt(jnp.sum(r2)) / (
        jnp.sqrt(jnp.sum(a2)) * jnp.linalg.norm(Z))
    return {"residual": residual,
            "orthogonality": jnp.sqrt(jnp.sum(g2) / n),
            "descents": jnp.sum(w[1:] < w[:-1]).astype(jnp.float32)}


def eig_flops(n: int) -> float:
    """Flops the one-stage algorithm needs for all n eigenpairs, from its
    shapes: 14 n^3 / 3.

    * 4 n^3 / 3, the Householder tridiagonalization: column j makes one
      symmetric matvec with the trailing matrix of order m = n - j - 1
      (2 m^2) and its share of the rank-2k update (2 m^2 more, counting
      the triangle alone); the sum of 4 m^2 over m is 4 n^3 / 3.
    * 4 n^3 / 3, the divide and conquer's eigenvector products done
      without deflation, as this program does them: a merge to order m
      multiplies blockdiag(Q1, Q2) (two m/2 x m/2 blocks) by the m x m
      secular eigenvector matrix, m^3 flops; n / m merges a level give
      n m^2, and m = n, n/2, n/4, ... sum to 4 n^3 / 3.  (The secular
      equations themselves are O(n^2) a level.)
    * 2 n^3, the back-transformation: n - 1 reflectors applied to n
      columns in blocked form, 4 (n - s) nb n flops a panel at offset s.
    """
    return 14.0 * float(n) ** 3 / 3.0


def hemv_bytes(n: int, itemsize: int = 4) -> float:
    """The least a one-stage reduction can read for its matvecs: the
    stored triangle of the TRUE trailing matrix, once a column.  Column j
    (0 .. n-2) multiplies the trailing matrix of order m = n - 1 - j,
    m (m + 1) / 2 stored entries; summed, (n - 1) n (n + 1) / 6 entries."""
    return itemsize * (n - 1.0) * n * (n + 1.0) / 6.0
