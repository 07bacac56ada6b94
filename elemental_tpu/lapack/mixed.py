"""Mixed-precision dense solve: LU WITHOUT pivoting whose trailing updates
run in ONE bfloat16 pass, refined on the device to the float32 limit.

Reference: the HPL-MxP benchmark (hpl-mxp.org, formerly HPL-AI): factor
A in low precision without pivoting (A is such that none is needed), then
refine the answer with the low-precision factors as the preconditioner
until it is as accurate as the high-precision solve.  The LAPACK sibling
is ``dsgesv`` (factor in single, refine in double).

Two precisions, kept apart:

* the LOW side is fixed: the operands of every trailing update
  ``A22 -= L21 U12`` are rounded to :data:`LOW` (bfloat16) EXPLICITLY and
  the product accumulates in float32 (``preferred_element_type``), so the
  CPU and the chip do the same arithmetic (``precision=`` on float32
  operands, what ``lu(update_precision=)`` passes, is one bf16 pass on a
  TPU and full float32 on the CPU) and the update reads half the panels'
  bytes;
* the HIGH side is ``precision`` (default ``Precision.HIGHEST``, resolved
  by :func:`~elemental_tpu.lapack.lu._hi`): the diagonal blocks, the two
  panel solves, the triangular sweeps and the residual.

The factor (:func:`lu_nopiv`) is a blocked right-looking loop over a COPY
of A (the residual needs A): per step the diagonal block's unpivoted LU
(``lu._lu_nopiv``: sub-blocks joined by two triangular solves and a
matmul; a sub-block's column recurrence is, on ONE TPU chip with real
float32, ONE Pallas kernel with the sub-block resident in VMEM,
``kernels/lu_nopiv_block.py``, and a ``fori_loop`` of XLA ops everywhere
else: :func:`_diag_blocks_in_vmem`, one algorithm in two lowerings), the
two panels as products with the block's triangular inverses
(``L21 = A21 U11^-1``, ``U12 = L11^-1 A12``; ``lu._tri_matmul``: over the
inverses' non-zero blocks only, 5/8 of the dense product's flops at nb
2048), and the trailing update.
On one chip it is built as ``cholesky._local_chol_array`` is: ONE n x n
working buffer addressed by static offsets and written in
place, the update in column stripes.  On a grid it is ``lu``'s distributed
loop with the panel factored unpivoted (CALU's refactorization without its
tournament and without ``move_rows``): the panel's columns gathered to
every chip, the row block solved on ``[STAR,VR]``, the update a local
product of ``[MC,STAR]`` and ``[STAR,MR]`` storage.  The operand is never
gathered whole.

The refinement (:func:`mixed_solve`) is one ``lax.while_loop`` inside the
same program: ``R = B - A X`` (stationary-A ``gemm``: A never moves),
``D = U^-1 L^-1 R`` (two ``trsm``), ``X += D``.  The stopping rule is the
program's own: the loop ends when a step no longer lowers ``||R||_F`` by
the factor :data:`FALL`, or after ``max_steps`` corrections; a correction
that does not lower the residual at all is not applied.

Scopes (grammar: :mod:`elemental_tpu.obs`): ``el.mixed_solve`` opens
``factor`` (under it ``el.lu_nopiv/k<step>/diag``, ``/panel``,
``/update``; the kernel's launches, ``el_lu_nopiv_block``, sit under the
step's ``diag``), ``sweeps`` (the first solve's two ``el.trsm``) and
``el.refine``, whose phases are ``k00/residual`` (the first residual and
A's norm) and, INSIDE the loop's body, ``k01/correct`` and
``k01/residual``: the first ``k<step>`` gives an op its phase, so the
correction's ``trsm`` and the residual's ``gemm`` read ``refine/correct``
and ``refine/residual``, not ``sweep``, ``update`` or ``panel``.
Counters: ``lu_nopiv_step`` (one a step of the factor),
``lu_nopiv_diag{impl}`` (one a diagonal block, beside it: ``kernel`` |
``xla``, the lowering its column recurrence took) and
``mixed_update{dtype}`` (one a trailing update, with the dtype its
operands were rounded to).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ..core.dist import MC, MR, STAR, VR
from ..core.distmatrix import DistMatrix
from ..core.view import view, update_view
from ..redist.engine import redistribute
from ..blas.level1 import frobenius_norm as _norm
from ..blas.level3 import _blocksize, _check_mcmr, gemm, trsm
from ..kernels import lu_nopiv_block
from ..obs import metrics as _metrics
from ..obs.tracer import phase_hook as _phase_hook, scoped as _scoped
from .lu import (_hi, _lu_nopiv, _nopiv_panel, _tri_matmul, _unit_lower_inv,
                 _upper_inv)

#: what the trailing updates' operands are rounded to: the low side
LOW = jnp.bfloat16
#: a refinement step counts as progress while it lowers ||R||_F by this
FALL = 0.25
#: corrections at most, where the caller gives no ``max_steps``
MAX_STEPS = 8


def _low_product(L21, U12, low, precision):
    """``L21 @ U12`` in float32 from operands rounded to ``low``; with
    ``low=None`` the plain product at ``precision`` (the float32 row the
    mixed one is compared with)."""
    if low is None:
        return jnp.matmul(L21, U12, precision=precision)
    return jnp.matmul(L21, U12, preferred_element_type=jnp.float32)


def _round(x, low):
    return x if low is None else x.astype(low)


def _tick_update(low, dtype):
    _metrics.inc("mixed_update", dtype=jnp.dtype(low or dtype).name)


def _unit_lower(Wf):
    return jnp.tril(Wf, -1) + jnp.eye(Wf.shape[0], dtype=Wf.dtype)


def _diag_blocks_in_vmem(A: DistMatrix) -> bool:
    """The rule of the factor's diagonal blocks, from what the input shows:
    the grid is ONE chip (the grid loop factors its panel whole through
    ``lu._nopiv_panel``, on every chip the same replicated block; a Mosaic
    kernel there wants a ``shard_map`` of its own, and no cell has timed
    that loop), the chip is a TPU (true of a described topology too, so a
    rehearsal takes the path; on the CPU the kernel would be interpreted)
    and the entries are real float32 (Mosaic has no complex type and no
    float64; the kernel's divide and multiply-subtract are float32 on the
    VPU, no lower than any ``precision``).  Then the unblocked column
    recurrence of each sub-block of ``lu._lu_nopiv`` is the Pallas kernel
    ``el_lu_nopiv_block``, the sub-block resident in VMEM (measured,
    PERF.md 6, PR 46); everything else keeps the ``fori_loop`` of XLA
    ops."""
    return (A.grid.size == 1 and A.grid.devices[0].platform == "tpu"
            and A.dtype == jnp.float32)


def _lu_nopiv_array(a, n: int, ib: int, precision, low, tm, block_kernel):
    """Packed unpivoted LU of an (n, n) array in one working buffer: step
    k reads its diagonal block, its two panels and its trailing window of
    ``T`` by static offsets and writes each back where it was read.  The
    update goes by column stripes ``2 ib`` wide, each one matmul of the
    rounded panels (rounded once a step) written into its own window.
    ``block_kernel`` is the diagonal blocks' lowering (:func:`lu_nopiv`)."""
    dt = a.dtype
    q = 2 * ib
    T = a
    for k, s in enumerate(range(0, n, ib)):
        w = min(ib, n - s)
        o = s + w
        _metrics.inc("lu_nopiv_step")
        _metrics.inc("lu_nopiv_diag",
                     impl="xla" if block_kernel is None else "kernel")
        # every read is of the LATEST value of T: a read of an older one
        # after a write costs a copy of the whole buffer (PERF.md 6, PR 34);
        # the panels go back by plain update-slices (``.at[].set`` goes
        # through a bounds select, which at step 0 cost a panel-sized
        # float32 value beside the blocks: PERF.md 6, PR 47)
        with tm.phase("diag", k) as ph:
            Wf = _lu_nopiv(T[s:o, s:o], precision,
                           block_kernel=block_kernel)
            ph.done(Wf)
        if o == n:
            with tm.phase("diag", k):
                T = T.at[s:o, s:o].set(Wf)
            break
        with tm.phase("panel", k) as ph:
            Ui = _upper_inv(jnp.triu(Wf), w, precision)
            L21 = _tri_matmul(T[o:, s:o], Ui, "right", precision).astype(dt)
            T = lax.dynamic_update_slice(
                T, jnp.concatenate([Wf, L21], axis=0), (s, s))
            Li = _unit_lower_inv(_unit_lower(Wf), w, precision)
            U12 = _tri_matmul(Li, T[s:o, o:], "left", precision).astype(dt)
            T = lax.dynamic_update_slice(T, U12, (s, o))
            # rounded once a step, and under the panels' name: the pass
            # that joins a panel's blocks and rounds them is the panel's
            Lb, Ub = _round(L21, low), _round(U12, low)
            ph.done(T)
        _tick_update(low, dt)
        with tm.phase("update", k) as ph:
            for i in range(0, n - o, q):
                j = min(i + q, n - o)
                upd = _low_product(Lb, Ub[:, i:j], low, precision)
                T = T.at[o:, o + i:o + j].set(
                    T[o:, o + i:o + j] - upd.astype(dt))
            ph.done(T)
    return T


def _lu_nopiv_grid(A: DistMatrix, ib: int, precision, low, tm) -> DistMatrix:
    """The distributed loop: per step the panel's columns gathered to
    every chip and factored unpivoted there (replicated, deterministic),
    the row block solved on [STAR,VR], the trailing update a local product
    of [MC,STAR] and [STAR,MR] storage with both rounded to ``low``."""
    n = A.gshape[0]
    g = A.grid
    for k, s in enumerate(range(0, n, ib)):
        e = min(s + ib, n)
        w = e - s
        _metrics.inc("lu_nopiv_step")
        _metrics.inc("lu_nopiv_diag", impl="xla")
        with tm.phase("panel", k) as ph:
            pan = redistribute(view(A, rows=(s, n), cols=(s, e)), STAR, STAR)
            Pf = _nopiv_panel(pan.local, w, precision)
            Pf_ss = DistMatrix(Pf, (n - s, w), STAR, STAR, 0, 0, g)
            A = update_view(A, redistribute(Pf_ss, MC, MR), rows=(s, n),
                            cols=(s, e))
            ph.done(A)
        if e == n:
            break
        with tm.phase("solve", k) as ph:
            Li = _unit_lower_inv(_unit_lower(Pf[:w]), w, precision)
            A12 = redistribute(view(A, rows=(s, e), cols=(e, n)), STAR, VR)
            u12 = _tri_matmul(Li, A12.local, "left", precision
                              ).astype(A.dtype)
            U12 = redistribute(A12.with_local(u12), STAR, MR)
            A = update_view(A, redistribute(U12, MC, MR), rows=(s, e),
                            cols=(e, n))
            ph.done(A)
        _tick_update(low, A.dtype)
        with tm.phase("update", k) as ph:
            L21 = redistribute(
                DistMatrix(Pf[w:], (n - e, w), STAR, STAR, 0, 0, g),
                MC, STAR)
            A22 = view(A, rows=(e, n), cols=(e, n))
            upd = _low_product(_round(L21.local, low),
                               _round(U12.local, low), low, precision)
            A = update_view(A, A22.with_local(
                A22.local - upd.astype(A.dtype)), rows=(e, n), cols=(e, n))
            ph.done(A)
    return A


@_scoped("el.lu_nopiv")
def lu_nopiv(A: DistMatrix, nb: int | None = None, precision=None,
             low=LOW) -> DistMatrix:
    """Packed LU of a square [MC,MR] matrix WITHOUT pivoting: unit-lower L
    strictly below the diagonal, U on and above it, ``L U = A`` with no
    permutation.  Only for operands on which that is stable (diagonally
    dominant, or with a positive definite symmetric part); anything else
    shows as growth in the factor, which :func:`mixed_solve` reports.

    ``low`` is what the trailing updates' operands are rounded to
    (float32 accumulation); ``None`` keeps them float32 at ``precision``.
    A is not written: the factor is a new matrix."""
    _check_mcmr(A)
    n = A.gshape[0]
    if A.gshape != (n, n):
        raise ValueError(f"lu_nopiv: a square matrix, got {A.gshape}")
    precision = _hi(precision)
    tm = _phase_hook("lu_nopiv")
    tm.start()
    g = A.grid
    if g.size == 1:
        # the diagonal blocks' lowering, decided once from the input and
        # static from here down (None: lu._lu_nopiv's own loop of XLA
        # ops); compiled on a TPU, a described one too, and interpreted
        # where a test patches the rule on the CPU
        block_kernel = None
        if _diag_blocks_in_vmem(A):
            block_kernel = partial(lu_nopiv_block,
                                   interpret=g.devices[0].platform != "tpu")
        return A.with_local(_lu_nopiv_array(
            A.local, n, max(nb or 2048, 1), precision, low, tm,
            block_kernel))
    ib = _blocksize(nb, math.lcm(g.height, g.width), n)
    return _lu_nopiv_grid(A, ib, precision, low, tm)


def _solve_after(LU_: DistMatrix, B: DistMatrix, nb, precision) -> DistMatrix:
    """``U^-1 L^-1 B`` from the packed factor: two ``trsm``, the lower one
    with an implicit unit diagonal."""
    Y = trsm("L", "L", "N", LU_, B, unit=True, nb=nb, precision=precision)
    return trsm("L", "U", "N", LU_, Y, nb=nb, precision=precision)


def _residual(A: DistMatrix, X: DistMatrix, B: DistMatrix, nb, precision):
    """``B - A X`` with A stationary: X's rows go to A's columns' order,
    one local product, one sum over the grid's rows.  A never moves."""
    return gemm(A, X, alpha=-1.0, beta=1.0, C=B, alg="A", nb=nb,
                precision=precision)


@_scoped("el.mixed_solve")
def mixed_solve(A: DistMatrix, B: DistMatrix, nb: int | None = None,
                precision=None, max_steps: int | None = None):
    """Solve A X = B to the float32 limit from a factorization whose
    trailing updates ran in ONE bfloat16 pass: :func:`lu_nopiv` of a copy
    of A (no pivoting: A must not need it), ``X0 = U^-1 L^-1 B``, then
    iterative refinement with the residual in float32, all in one
    program (module docstring).  Returns ``(X, info)``:

    * ``info["steps"]``: corrections computed (int32; decided on the
      device: the loop ends when a step lowers ``||B - A X||_F`` by less
      than :data:`FALL`, or at ``max_steps``, default :data:`MAX_STEPS`;
      ``max_steps=0`` returns the unrefined ``X0``);
    * ``info["backward_error"]``: ``||B - A X||_F / (||A||_F ||X||_F +
      ||B||_F)`` of the X returned, in float32;
    * ``info["converged"]``: that number is finite and at most float32's
      eps.  An operand that needed pivoting (growth in the factor, a zero
      pivot) reads False here instead of handing back a wrong X silently.

    ``precision`` is the HIGH side (panels, sweeps, residual; ``None`` =
    ``Precision.HIGHEST``); the low side is fixed (:data:`LOW`)."""
    return _mixed_solve(A, B, nb, precision, max_steps, LOW)


def _mixed_solve(A, B, nb, precision, max_steps, low):
    _check_mcmr(A, B)
    precision = _hi(precision)
    cap = MAX_STEPS if max_steps is None else max(int(max_steps), 0)
    with jax.named_scope("factor"):
        LU_ = lu_nopiv(A, nb=nb, precision=precision, low=low)
    with jax.named_scope("sweeps"):
        X = _solve_after(LU_, B, nb, precision)
    with jax.named_scope("el.refine"):
        tm = _phase_hook("refine")
        with tm.phase("residual", 0):
            R = _residual(A, X, B, nb, precision)
            an, bn, rn = _norm(A), _norm(B), _norm(R)

        def body(state):
            X, R, rn, steps, _ = state
            with tm.phase("correct", 1):
                D = _solve_after(LU_, R, nb, precision)
                Xn = X.with_local(X.local + D.local)
            with tm.phase("residual", 1):
                Rn = _residual(A, Xn, B, nb, precision)
                rnn = _norm(Rn)
                better = rnn < rn          # False for a NaN, too
                X, R = (M.with_local(jnp.where(better, Mn.local, M.local))
                        for M, Mn in ((X, Xn), (R, Rn)))
            return (X, R, jnp.where(better, rnn, rn), steps + 1,
                    rnn < FALL * rn)

        def cond(state):
            return (state[3] < cap) & state[4]

        X, R, rn, steps, _ = lax.while_loop(
            cond, body, (X, R, rn, jnp.int32(0), jnp.bool_(cap > 0)))
        berr = rn / (an * _norm(X) + bn)
    info = {"steps": steps, "backward_error": berr,
            "converged": jnp.isfinite(berr)
            & (berr <= jnp.finfo(jnp.float32).eps)}
    return X, info
