"""Shared plumbing for the fused Pallas panel kernels (ISSUE 17).

Panel factorization is replicated-local compute: every rank holds the
whole [STAR,STAR] panel and runs the same serial column recurrence, so
the fusion problem is purely single-chip -- keep the panel resident in
VMEM, run the recurrence as one kernel body, and emit the packed factor
in a single store.  This module holds what all three kernels share:

* tile-aligned padding: float32 VMEM tiles are (sublane, lane) =
  (8, 128), so inputs are padded up to tile multiples and the column
  recurrences run only over the real extent -- the padding is zeros
  that never reach a pivot decision or a stored factor entry (padded
  rows are masked out of argmax candidates; padded columns only ever
  receive exact-zero updates);
* the VMEM residency budget that gates whole-panel fusion, and the
  scoped-VMEM limit every kernel is compiled with: a panel whose working
  set cannot fit stays on the XLA ladder.  Honesty about applicability
  is what keeps the ``panel_impl='auto'`` cost term truthful -- the gate
  says "no" wherever the compiler would;
* the interpret-mode decision: on the CPU backend the kernels run under
  ``pl.pallas_call(interpret=True)`` so CPU CI executes the very same
  kernel bodies -- identical LU pivot sequence, residual-bounded factors
  -- against their XLA twins; on a TPU backend a kernel is compiled or
  the call fails.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.pallas import tpu as pltpu

#: float32 VMEM tile extents (sublane x lane); narrower dtypes pack more
#: sublanes but (8, 128) alignment is valid for every dtype we ship.
SUBLANE = 8
LANE = 128

#: What the gate in :meth:`PanelPlan.use_pallas` prices one panel's
#: resident refs at (input + packed output + the kernel's square
#: outputs/scratch): ``copies`` tile-padded panels must fit this.
PANEL_VMEM_BUDGET = 16 * 2 ** 20

#: The scoped-VMEM limit every panel kernel asks the compiler for.  The
#: compiler's own default (16 MiB on a v5e, of 128 MiB physical) refuses
#: the largest shapes the gate admits -- besides the refs the gate
#: prices, Mosaic stacks full-panel temporaries of its own (a 5456 x 256
#: LU panel, 5.3 MiB, needs 26 MiB) -- so the limit is set explicitly,
#: with room: every corner of the gate compiles under it
#: (``tests/test_chip_compile.py``).
PANEL_VMEM_LIMIT = 64 * 2 ** 20


def round_up(n: int, m: int) -> int:
    return -(-max(int(n), 1) // m) * m


def interpret_default(interpret=None) -> bool:
    """Resolve the ``interpret=`` tristate per backend.  TPU: the kernel
    is compiled, and asking to interpret it there is an error.  CPU:
    interpreted unless the caller says otherwise (``interpret=False`` is
    how a kernel is lowered for a described chip).  Any other backend has
    no lowering of these kernels and is refused."""
    backend = jax.default_backend()
    if backend == "tpu":
        if interpret:
            raise ValueError("panel kernels are compiled on a tpu backend; "
                             "interpret mode is for the CPU tests")
        return False
    if backend != "cpu":
        raise NotImplementedError(
            f"panel kernels have no lowering for backend {backend!r}")
    return True if interpret is None else bool(interpret)


def loop32(lo: int, hi: int, body):
    """``fori_loop`` over the static range [lo, hi) with an int32 index
    and no carry (the kernels keep their state in refs).  Under
    ``jax_enable_x64`` a Python-int bound would make the index int64,
    which Mosaic has no lowering for."""
    return lax.fori_loop(jnp.int32(lo), jnp.int32(hi), body, 0)


def kernel_trace(interpret: bool):
    """Context the ``pallas_call`` is traced in.  Mosaic has no 64-bit
    types, and under ``jax_enable_x64`` every Python scalar in a kernel
    body is traced as a 64-bit constant: a kernel that is to be COMPILED
    is traced with x64 off (its operands are 32-bit or the TPU could not
    hold them anyway).  The interpreter takes float64 panels as they are."""
    return contextlib.nullcontext() if interpret else jax.enable_x64(False)


def compiler_params(dimension_semantics=None):
    """Mosaic parameters every kernel is compiled with: the scoped VMEM
    limit is raised from the compiler's default to
    :data:`PANEL_VMEM_LIMIT`.  A kernel with a grid names each grid
    dimension's semantics (``arbitrary``: the steps run in order and may
    carry state from one to the next)."""
    return pltpu.CompilerParams(vmem_limit_bytes=PANEL_VMEM_LIMIT,
                                dimension_semantics=dimension_semantics)


def pad_tiles(x):
    """Zero-pad a 2-D operand up to (SUBLANE, LANE) tile multiples."""
    m, n = x.shape
    mp, np_ = round_up(m, SUBLANE), round_up(n, LANE)
    if (mp, np_) == (m, n):
        return x
    return jnp.pad(x, ((0, mp - m), (0, np_ - n)))


def panel_fits(shape, dtype, copies: int = 3,
               budget: int = PANEL_VMEM_BUDGET) -> bool:
    """Static gate: does ``copies`` tile-padded residents of this panel
    fit the VMEM budget?  Evaluated per call site at trace time (shapes
    are static), so the xla/pallas choice is baked into the jaxpr."""
    mp = round_up(shape[0], SUBLANE)
    np_ = round_up(shape[1], LANE)
    return copies * mp * np_ * jnp.dtype(dtype).itemsize <= budget
