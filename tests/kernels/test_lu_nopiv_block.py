"""The unpivoted block LU kernel alone (ISSUE 46), interpreted on the CPU:
``el_lu_nopiv_block`` against a float64 numpy unpivoted LU and against its
XLA twin (``lapack.lu._lu_nopiv``'s unblocked loop) on the same block, on
blocks like the HPL-MxP cell's (entries in [-1, 1), diagonal 2 sqrt(n): no
pivoting needed), over orders that are one sublane tile, one lane tile,
ragged and the sub-block order ``_lu_nopiv`` ships; ``L U = B``;
``_lu_nopiv``'s blocked outer loop around it; the padding never reaching a
stored entry; a complex block refused.  The kernel's speed is
the chip's to say, its lowering ``tests/test_chip_compile.py``'s.
"""
import numpy as np
import pytest

import functools
import inspect

import jax
import jax.numpy as jnp

from elemental_tpu.kernels import lu_nopiv_block
from elemental_tpu.lapack.lu import _lu_nopiv

#: the sub-block order ``_lu_nopiv`` ships, for both of its lowerings
BS = inspect.signature(_lu_nopiv).parameters["bs"].default

#: one sublane tile; one lane tile; ragged in both (200 = 25 sublane tiles,
#: 72 columns past a lane tile); two lane tiles; the order shipped
ORDERS = sorted({8, 128, 200, 256, BS})

#: one compile for each shape: an interpreted kernel's cost here
_kernel = jax.jit(lu_nopiv_block)
#: the XLA twin: ``_lu_nopiv`` below its own blocking is ``unb`` alone
_twin = jax.jit(lambda B: _lu_nopiv(B, None, bs=max(ORDERS)))


def _block(n, dtype, seed=0):
    """A block like ``reference_mxp.entry_shifted_pm1``'s diagonal blocks."""
    rng = np.random.default_rng(seed + n)
    B = rng.uniform(-1.0, 1.0, size=(n, n))
    B[np.diag_indices(n)] = 2.0 * np.sqrt(n)
    return B.astype(dtype)


def _lu64(B):
    """The same recurrence in float64 numpy."""
    B = np.asarray(B, np.float64).copy()
    for j in range(B.shape[0]):
        B[j + 1:, j] /= B[j, j]
        B[j + 1:, j + 1:] -= np.outer(B[j + 1:, j], B[j, j + 1:])
    return B


def _tol(n, dtype):
    # an entry is a sum of at most n products of an entry of L (under
    # 1 / sqrt(n)) and one of U (under 2 sqrt(n) + 1): a few ulps of the
    # diagonal's scale, 2 sqrt(n), for each of them
    return 4 * n * np.finfo(dtype).eps * 2 * np.sqrt(n)


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("n", ORDERS)
def test_kernel_agrees_with_float64_numpy_and_with_its_xla_twin(n, dtype):
    B = _block(n, dtype)
    got = _kernel(jnp.asarray(B))
    assert got.shape == (n, n) and got.dtype == dtype
    got = np.asarray(got, np.float64)
    assert np.abs(got - _lu64(B)).max() <= _tol(n, dtype)
    # the twin is the same arithmetic; whether ``a - l u`` rounds once or
    # twice is each backend's to decide, so to a few ulps and not to the bit
    twin = np.asarray(_twin(jnp.asarray(B)), np.float64)
    assert np.abs(got - twin).max() <= _tol(n, dtype) / 4


@pytest.mark.parametrize("n", ORDERS)
def test_packed_factor_multiplies_back_to_the_block(n):
    """``L U = B`` with unit-lower L below the diagonal and U on and above
    it, no permutation: to 8 eps in the Frobenius norm (float32)."""
    B = _block(n, np.float32, seed=1)
    F = np.asarray(_kernel(jnp.asarray(B)), np.float64)
    L, U = np.tril(F, -1) + np.eye(n), np.triu(F)
    assert np.linalg.norm(L @ U - B) <= 8 * np.finfo(np.float32).eps \
        * np.linalg.norm(B)


@pytest.mark.parametrize("n,bs", [(200, 64), (200, 128), (384, 128),
                                  (384, BS), (512, BS), (520, BS)])
def test_blocked_outer_loop_is_the_same_around_either_lowering(n, bs):
    """``_lu_nopiv`` above its sub-block order: sub-blocks (the last one
    ragged at 200, 384 and 520) through the kernel, the two triangular
    solves and the matmul between them as they were.  Interpreted here the
    kernel gives its twin's bits, so the blocked factors are equal to the
    bit; and both are the float64 recurrence's."""
    B = _block(n, np.float32, seed=2)
    kernel = functools.partial(lu_nopiv_block, interpret=True)
    got = np.asarray(jax.jit(lambda b: _lu_nopiv(
        b, None, bs, block_kernel=kernel))(jnp.asarray(B)))
    twin = np.asarray(jax.jit(lambda b: _lu_nopiv(b, None, bs))(
        jnp.asarray(B)))
    assert np.array_equal(got, twin)
    assert np.abs(got - _lu64(B)).max() <= _tol(n, np.float32)


@pytest.mark.parametrize("n", [5, 72, 200])
def test_padding_never_reaches_a_stored_entry(n):
    """The block is zero-padded to (8, 128) tiles.  A leading principal
    block of a larger block has the larger block's leading factor (LU
    without pivoting nests), so the kernel on the n x n corner, PADDED, must
    give the bits of the corner of the kernel on the whole, UNPADDED in
    those rows and columns: whatever the padding held took part in no
    stored entry."""
    big = _block(256, np.float32, seed=3)
    corner = np.asarray(lu_nopiv_block(jnp.asarray(big[:n, :n])))
    whole = np.asarray(_kernel(jnp.asarray(big)))
    assert np.array_equal(corner, whole[:n, :n])
    assert np.all(np.isfinite(corner))


@pytest.mark.parametrize("shape,dtype,match", [
    ((16, 16), np.complex64, "real-only"),
    ((16, 8), np.float32, "square"),
])
def test_refuses_what_it_cannot_factor(shape, dtype, match):
    with pytest.raises(ValueError, match=match):
        lu_nopiv_block(jnp.ones(shape, dtype))
