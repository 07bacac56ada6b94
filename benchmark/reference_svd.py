"""The yardstick's check and flop counts of a dense singular value
decomposition: all n triplets of a square matrix.

Plain ``jax.numpy`` at HIGHEST; imports ``reference`` (the operands: a
function of the global index and a 32-bit key) and nothing of
``elemental_tpu``, and takes nothing the program has made but its answer
``(U, s, V)``.  A is regenerated here a block of rows at a time.

Why these numbers are the whole check.  With ``V^T V = I``,
``U^T U = I`` and ``A V = U diag(s)`` to the limits,
``A = U diag(s) V^T`` to the same order: a decomposition of A with
orthonormal factors and a non-negative descending diagonal IS its SVD
(Weyl: every singular value of A is one of the ``s`` and none is missing
or doubled).  No float64 oracle of the full size is needed.  Two swapped
columns of V or one negated ``u_j`` break the residual, a repeated or
scaled column an orthogonality, an unsorted or negative ``s`` the count
of descents.
"""
import math

import jax
import jax.numpy as jnp

import reference

HIGHEST = reference.HIGHEST

#: the steps of a QDWH iteration whose c is over this take the QR form
QR_C_SWITCH = 100.0


def residuals_svd(entry, n, U, s, V, sharding=None):
    """The numbers ``correct`` is decided from, for the answer
    ``(U, s, V)`` (``s`` the n singular values, ``U`` and ``V`` the n x n
    singular vectors as ordinary arrays) of the operand ``entry``:

    * ``residual``: ||A V - U diag(s)||_F / (||A||_F ||V||_F)
    * ``orthogonality_u``, ``orthogonality_v``: ||Q^T Q - I||_F / sqrt(n)
    * ``descents``: how many i have s[i] < s[i+1], plus how many s[i] < 0
      (descending and non-negative: 0)
    * ``frobenius_defect`` (printed, not compared):
      |sum s_i^2 - ||A||_F^2| / ||A||_F^2

    A is regenerated, and the two Gram matrices formed,
    ``reference.BLOCK_ROWS`` rows at a time.
    """
    rows = min(reference.BLOCK_ROWS, n)
    if n % rows:
        raise ValueError(f"n = {n} is not a multiple of {rows} rows")
    if U.shape != (n, n) or V.shape != (n, n) or s.shape != (n,):
        raise ValueError(f"all triplets of order {n} are U {(n, n)}, s "
                         f"{(n,)} and V {(n, n)}; got {U.shape}, {s.shape} "
                         f"and {V.shape}")
    i = jax.lax.broadcasted_iota(jnp.int32, (rows, n), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (rows, n), 1)

    def gram_rows(Q, b):
        # rows b of Q^T Q - I: (columns b of Q)^T Q
        Qc = jax.lax.dynamic_slice_in_dim(Q, b * rows, rows, axis=1)
        G = jnp.matmul(Qc.T, Q, precision=HIGHEST)
        return G - (b * rows + i == j).astype(G.dtype)

    def block(b):
        A = reference.plain_block(entry, b * rows, rows, n, sharding)
        Ub = jax.lax.dynamic_slice_in_dim(U, b * rows, rows, axis=0)
        R = jnp.matmul(A, V, precision=HIGHEST) - Ub * s[None, :]
        Gu, Gv = gram_rows(U, b), gram_rows(V, b)
        return (jnp.sum(R * R), jnp.sum(A * A), jnp.sum(Gu * Gu),
                jnp.sum(Gv * Gv))

    r2, a2, gu2, gv2 = jax.lax.map(
        block, jnp.arange(n // rows, dtype=jnp.int32))
    a2 = jnp.sum(a2)
    residual = jnp.sqrt(jnp.sum(r2)) / (jnp.sqrt(a2) * jnp.linalg.norm(V))
    descents = jnp.sum(s[:-1] < s[1:]) + jnp.sum(s < 0)
    return {"residual": residual,
            "orthogonality_u": jnp.sqrt(jnp.sum(gu2) / n),
            "orthogonality_v": jnp.sqrt(jnp.sum(gv2) / n),
            "descents": descents.astype(jnp.float32),
            "frobenius_defect": jnp.abs(jnp.sum(s * s) - a2) / a2}


def qdwh_step_kinds(eps: float = 2.0 ** -23, maxiter: int = 32):
    """``['qr', 'qr', 'chol', ...]``: the step kinds of the QDWH iteration
    from the lower bound ``l0 = eps`` to ``1 - l <= 10 eps``, then the two
    plain Halley steps that end it (Nakatsukasa, Bai, Gygi 2010, the
    dynamically weighted parameters; a step whose c is over
    ``QR_C_SWITCH`` is QR-based).  The recurrence reads no data, so the
    kinds are a function of the precision alone: float32's eps gives 2
    QR-based and 4 Cholesky-based steps."""
    kinds, l = [], float(eps)
    while 1.0 - l > 10 * eps and len(kinds) < maxiter:
        l2 = l * l
        dd = (4.0 * (1.0 - l2) / (l2 * l2)) ** (1.0 / 3.0)
        sqd = math.sqrt(1.0 + dd)
        a = sqd + 0.5 * math.sqrt(
            max(8.0 - 4.0 * dd + 8.0 * (2.0 - l2) / (l2 * sqd), 0.0))
        b = (a - 1.0) ** 2 / 4.0
        c = a + b - 1.0
        kinds.append("qr" if c > QR_C_SWITCH else "chol")
        l = l * (a + b * l2) / (1.0 + c * l2)
    return kinds + ["chol", "chol"]         # c = 3


def polar_flops(n: int, kinds) -> float:
    """Flops of the polar stage of the QDWH-SVD of an n x n matrix whose
    iteration takes the steps ``kinds``, from the shapes, the plain forms
    (no use of the identity block's zeros):

    * a QR-based step, 34 n^3 / 3: Householder QR of the (2n x n) stack
      ``[sqrt(c) X; I]``, 2 n^2 (2n - n/3) = 10 n^3 / 3; its thin Q as the
      reflectors applied to a (2n x n) identity, 4 (2n - s) nb n a panel
      at offset s, 6 n^3; ``Q1 Q2^T``, 2 n^3;
    * a Cholesky-based step, 13 n^3 / 3: ``X^T X`` as a full square,
      2 n^3; its Cholesky factor, n^3 / 3; two triangular solves with n
      right-hand sides, n^3 each;
    * ``H = U_p^T A`` and ``U = U_p V``, 2 n^3 each.
    """
    n3 = float(n) ** 3
    step = {"qr": 34.0 / 3.0, "chol": 13.0 / 3.0}
    return (sum(step[k] for k in kinds) + 4.0) * n3


def svd_flops(n: int, kinds) -> float:
    """Flops of the whole QDWH-SVD: the polar stage and the Hermitian
    eigensolve of H by the one-stage route, 14 n^3 / 3
    (``reference_eig.eig_flops``: tridiagonalization 4/3, divide and
    conquer 4/3, back-transformation 2)."""
    return polar_flops(n, kinds) + 14.0 * float(n) ** 3 / 3.0
