"""The yardstick's operands, check, flop count and byte count of a
tall-skinny least-squares solve: minimize ||A X - B||_F, A m x n, m >> n.

Plain ``jax.numpy`` at HIGHEST; imports ``reference`` (the hash and the
block generator: an operand is a function of the global index and a
32-bit key) and nothing of ``elemental_tpu``, and takes nothing the
program has made but its answer X.  A and B are regenerated here a block
of rows at a time.

The operands.  Entries uniform in [-1, 1) give a matrix of condition 1.01
at a cell's shape.  The cell's A has GRADED columns (``entry_graded``,
ISSUE 43's):

    a(i, j) = h(i, j) 2^(-j/16),   h uniform in [-1, 1)

condition 6.3e4 as stored at n = 256, and still about 1 once the columns
are scaled to unit length.  The number compared is free of the columns'
scale, as Householder QR is; B is uniform noise, as every cell's.

Grading alone does NOT trouble the normal equations: a Cholesky
factorization is as accurate on D G D as on G (van der Sluis), so a
float32 Gram matrix of graded columns solves to the ``residual_angle``
Householder QR reads (``tests/test_library_lstsq.py``).  What breaks them
and not an orthogonal factorization is COLLINEARITY, which no column
scaling removes.  ``entry_graded_collinear`` is for that test, and for the
program's own tests, on the CPU: every column one common vector plus
2^-10 of its own noise, graded on top:

    a(i, j) = (c(i) + 2^-10 h(i, j)) 2^(-j/16),   c, h uniform in [-1, 1)

With the columns scaled to unit length its condition is about
sqrt(n) 2^10 (1.9e4 at n = 256: its square times float32's 6e-8 is 20,
and the Cholesky factorization of the float32 Gram matrix meets a
negative pivot); as stored it is about 2e8.
``tests/lapack/test_least_squares_tall.py`` measures the four condition
numbers in float64 at the test size.
"""
import jax
import jax.numpy as jnp

import reference

HIGHEST = reference.HIGHEST

#: each column's own noise beside the common vector
COLLINEAR = 2.0 ** -10
#: column j is scaled by 2^(-j / GRADE)
GRADE = 16.0
#: the column index (no column has it) the common vector is hashed at
_COMMON = 0x7FFFFFF1

#: rows of A the check holds at a time (67 MB of float32 at n = 256)
BLOCK_ROWS = 65536


def entry_graded(n, key):
    """f(i, j): uniform entries, column j scaled by 2^(-j / GRADE)."""
    del n

    def f(i, j):
        grade = jnp.exp2(-j.astype(jnp.float32) / jnp.float32(GRADE))
        return reference.hash_pm1(i, j, key) * grade
    return f


def entry_graded_collinear(n, key):
    """f(i, j): the collinear, graded operand of the module docstring."""
    del n

    def f(i, j):
        common = reference.hash_pm1(i, jnp.full_like(j, _COMMON), key)
        own = reference.hash_pm1(i, j, key)
        grade = jnp.exp2(-j.astype(jnp.float32) / jnp.float32(GRADE))
        return (common + jnp.float32(COLLINEAR) * own) * grade
    return f


def residuals_lstsq(entry_a, entry_b, m, n, nrhs, X, sharding=None,
                    block_rows=None):
    """The numbers ``correct`` is decided from, for the answer X (n x nrhs,
    an ordinary array), with r_k = b_k - A x_k:

    * ``residual_angle``: max over columns j and right-hand sides k of
      |a_j^T r_k| / (||a_j|| ||r_k||).  Zero at the minimizer (the
      residual is orthogonal to every column) and only there; free of
      the columns' scale.
    * ``residual_norm``: max over k of ||r_k|| / ||b_k|| (printed: B is
      noise, of which the fit explains n / m, so it reads just under 1).

    A and B are regenerated ``block_rows`` rows at a time (the last block
    masked to m rows), so the check holds a slice of each beside X."""
    rows = min(block_rows or BLOCK_ROWS, m)
    blocks = -(-m // rows)

    def block(b):
        A = reference.plain_block(entry_a, b * rows, rows, n, sharding)
        B = reference.plain_block(entry_b, b * rows, rows, nrhs)
        live = (b * rows + jax.lax.broadcasted_iota(
            jnp.int32, (rows, 1), 0)) < m
        A = jnp.where(live, A, 0)
        B = jnp.where(live, B, 0)
        R = B - jnp.matmul(A, X, precision=HIGHEST)
        return (jnp.matmul(A.T, R, precision=HIGHEST),
                jnp.sum(A * A, axis=0), jnp.sum(R * R, axis=0),
                jnp.sum(B * B, axis=0))

    atr, a2, r2, b2 = jax.lax.map(block, jnp.arange(blocks, dtype=jnp.int32))
    atr, a2, r2, b2 = (jnp.sum(v, axis=0) for v in (atr, a2, r2, b2))
    angle = jnp.abs(atr) / jnp.sqrt(a2[:, None] * r2[None, :])
    return {"residual_angle": jnp.max(angle),
            "residual_norm": jnp.max(jnp.sqrt(r2 / b2))}


def lstsq_flops(m: int, n: int, nrhs: int) -> float:
    """Flops a Householder least-squares solve needs, from its shapes:
    LAPACK's operation counts (LAWN 41) of the three routines ``gels``
    calls, leading terms.

    * ``geqrf``, 2 m n^2 - 2 n^3 / 3: reflector j (of n) is applied to the
      n - j - 1 columns right of it over m - j rows, 4 (m - j)(n - j - 1)
      flops (a dot and an axpy a column); the sum over j is
      2 m n^2 - 2 n^3 / 3 + O(m n).
    * ``ormqr``, 4 m n nrhs - 2 n^2 nrhs: Q^T B applies the same n
      reflectors to nrhs columns, 4 (m - j) nrhs each.
    * ``trtrs``, n^2 nrhs: one back substitution a right-hand side.
    """
    m, n, nrhs = float(m), float(n), float(nrhs)
    return (2.0 * m * n * n - 2.0 * n ** 3 / 3.0
            + 4.0 * m * n * nrhs - 2.0 * n * n * nrhs + n * n * nrhs)


def lstsq_bytes(m: int, n: int, nrhs: int, itemsize: int = 4) -> float:
    """The bytes no method can avoid: one read of A and of B, all chips'
    shards together."""
    return float(itemsize) * m * (n + nrhs)
