"""The lower-precision control at a size a test run can hold.

On the CPU ``Precision.HIGH`` is float32 like everything else, so the
program's own switch cannot play the control here (on the chip it does:
``control_on_chip.py``).  This is the contract's other form: a plain
blocked solve in numpy, put in the program's place, whose update
matmuls run either in float32 or as three bfloat16 passes (hi*hi +
hi*lo + lo*hi, what ``HIGH`` does on the MXU).  The number the benchmark
compares has to tell the two apart by a factor of three, on the operand
the cells use -- and does not on the Gershgorin operand the first draft
used, which is why the cells do not use it.
"""
import ml_dtypes
import numpy as np
import pytest

import reference

N, NB, NRHS = 512, 64, 8


def float32_matmul(a, b):
    return a @ b


def three_pass_matmul(a, b):
    def split(x):
        hi = x.astype(ml_dtypes.bfloat16).astype(np.float32)
        lo = (x - hi).astype(ml_dtypes.bfloat16).astype(np.float32)
        return hi, lo
    (ah, al), (bh, bl) = split(a), split(b)
    return ah @ bh + ah @ bl + al @ bh


def plain_hpd_solve(A, B, matmul):
    """Right-looking blocked Cholesky and two blocked sweeps, float32;
    every off-diagonal update goes through ``matmul``."""
    A, X = A.copy(), B.copy()
    n = len(A)
    for k in range(0, n, NB):
        e = k + NB
        A[k:e, k:e] = np.linalg.cholesky(A[k:e, k:e])
        A[e:, k:e] = np.linalg.solve(A[k:e, k:e], A[e:, k:e].T).T
        A[e:, e:] -= matmul(A[e:, k:e], A[e:, k:e].T)
    L = np.tril(A)
    for k in range(0, n, NB):                       # L Y = B
        e = k + NB
        X[k:e] = np.linalg.solve(L[k:e, k:e], X[k:e])
        X[e:] -= matmul(L[e:, k:e], X[k:e])
    for k in range(n - NB, -1, -NB):                # L^T X = Y
        e = k + NB
        X[k:e] = np.linalg.solve(L[k:e, k:e].T, X[k:e])
        X[:k] -= matmul(L[k:e, :k].T, X[k:e])
    return X


def operands(operand, seed):
    key = [np.uint32(reference.operand_key(seed, 0, w)) for w in (0, 1)]
    A = np.asarray(reference.plain_block(
        reference.ENTRIES[operand](N, key[0]), 0, N, N))
    B = np.asarray(reference.plain_block(
        reference.ENTRIES["uniform_pm1"](N, key[1]), 0, N, NRHS))
    return key, A, B


def backward_error(operand, key, X):
    out = reference.residuals(reference.ENTRIES[operand](N, key[0]),
                              reference.ENTRIES["uniform_pm1"](N, key[1]),
                              N, NRHS, X)
    return float(out["backward_error"])


@pytest.mark.parametrize("seed", [11, 2147483999, 3000000001])
def test_three_passes_read_three_times_the_float32_error(seed):
    key, A, B = operands("hpd_shifted", seed)
    sound = backward_error("hpd_shifted", key,
                           plain_hpd_solve(A, B, float32_matmul))
    control = backward_error("hpd_shifted", key,
                             plain_hpd_solve(A, B, three_pass_matmul))
    assert control > 3 * sound


def test_the_gershgorin_operand_hides_the_precision():
    key, A, B = operands("hpd_gershgorin", 11)
    sound = backward_error("hpd_gershgorin", key,
                           plain_hpd_solve(A, B, float32_matmul))
    control = backward_error("hpd_gershgorin", key,
                             plain_hpd_solve(A, B, three_pass_matmul))
    assert control < 1.5 * sound
