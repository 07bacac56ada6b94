"""Blocked distributed Cholesky + SPD solve, look-ahead pipelined.

Reference: Elemental ``src/lapack_like/factor/Cholesky.cpp`` +
``Cholesky/LVar3.hpp`` (blocked right-looking lower variant) and
``src/lapack_like/solve/HPDSolve.cpp`` (Cholesky + two triangular sweeps)
-- BASELINE.json's headline "SPD Ax=b" config.

Per panel (the LVar3 loop, SURVEY.md §4.2):
  A11 -> [STAR,STAR]            replicated diagonal block, local potrf
  A21 -> [VC,STAR]              1-D cyclic panel, local right-Trsm by L11^H
  (L21, L21^H) spread           fused engine ``panel_spread``: [MC,STAR]
                                and the [STAR,MR] adjoint in ONE collective
  A22 -= L21 L21^H (lower tri)  column stripes of the lower trapezoid,
                                each one storage matmul on the MXU, masked
                                on its diagonal block

Look-ahead schedule (default on; the Cholesky twin of lu.py's pipeline)
-----------------------------------------------------------------------
The classic right-looking driver serializes diag -> panel -> spread ->
update every step, leaving the latency-bound replicated ``_potrf_inv`` on
the critical path ``n/nb`` times.  The pipelined driver splits step k's
trailing update at the next panel boundary:

    write back L11_k                          (from the carried factor)
    (L21, L21^H) := panel_spread(L21_vc)      (one fused collective)
    write back L21_k
    strip := A22[:, :nb] - L21 L21^H[:, :nb]  (narrow column-strip update)
    factor diag block k+1 from ``strip``      (off the critical path)
    solve panel k+1 from ``strip``            (off the critical path)
    A22[:, nb:] -= L21 L21^H[:, nb:]          (wide MXU update, in stripes)

Step k+1's replicated ``_potrf_inv`` and panel solve read the ``strip``
VALUE, the wide remainder update reads its own window of the factor and
neither of their results, so the two share no data dependence and XLA is
free to overlap them.  Every window is written where it was just read
(one working copy of the shard, updated in place; PERF.md 6, PR 35).
``lookahead=False`` keeps the classic order -- the same factor (to the bit
where the backend's dot does not choose its kernel by the product's shape).
Either order updates only the lower trapezoid, by column stripes ``2 nb``
wide (``chol_update_stripe`` counts them): one product over the square window
with its upper half masked away was half the update's flops discarded at
the matmuls' roofline (PERF.md 6, PR 36).

Tail crossover-to-local (``crossover``)
---------------------------------------
The shrinking tail pays full per-step redistribution latency on ever
smaller trailing matmuls.  Once the trailing matrix drops to ``crossover``
(default :data:`_CROSSOVER` when look-ahead is on; 0 disables), it is
gathered ONCE to [STAR,STAR] and finished with the replicated sequential
schedule (:func:`_local_chol_array`) -- O(t^3) redundant flops on every
device, but zero further collectives.  ``crossover=None`` picks the
default; pass an int to override.

Phases (``timer``)
------------------
The driver marks diag / panel / spread / update (/tail) with the scoped
form of its hook (``with tm.phase(phase, k)``, :mod:`elemental_tpu.obs`):
under ``jit`` the compiled program's ops carry
``el.cholesky/k<step>/<phase>`` in their names, which is what a device
trace is split by.  Pass an ``elemental_tpu.obs.PhaseTimer`` and call
``cholesky`` EAGERLY and the same blocks also charge per-step wall-clock
(same ``phase_timings/v1`` schema as LU).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.layout import Layout, with_layout_constraint

from ..core.dist import MC, MR, VC, STAR
from ..core.distmatrix import DistMatrix
from ..core.view import view, update_view
from ..redist.engine import (apply_fault, redistribute, transpose_dist,
                             panel_spread)
from ..redist.quantize import check_comm_precision
from ..blas.level1 import make_trapezoidal, _global_indices
from ..blas.level3 import _blocksize, _check_mcmr, trsm
from ..obs import metrics as _metrics
from ..obs.tracer import NULL_HOOK, scoped as _scoped
from .lu import _hi, _phase_hook, _tri_matmul

#: Trailing-matrix size at which the distributed loop gathers the tail and
#: finishes locally (look-ahead schedule only, unless overridden).  The
#: per-step cost floor of the distributed loop is ~3 collective rounds; at
#: t <= ~4k the whole remaining O(t^3/3) factors locally in less time than
#: the remaining t/nb rounds cost (a count of rounds, not a chip reading:
#: no ledger line has run another value).
_CROSSOVER = 4096


def _potrf_inv(D, precision, bs: int = 512, plan=None):
    """:func:`_potrf_inv_impl` routed through the engine's ``'compute'``
    fault seam (identity unless a FaultPlan is installed -- ISSUE 9):
    the diagonal-block factor/inverse pair IS cholesky's local panel
    math, so corrupting it here models a soft error in local compute.

    ``plan`` (a ``kernels.PanelPlan``) selects the implementation: the
    fused Pallas kernel (``kernels.potrf_inv`` -- blocked potrf +
    triangular inverse in ONE launch) when the resolved ``panel_impl``
    says so and the block passes the static VMEM/dtype gate; else the
    XLA path.  Both land on the same fault seam."""
    if plan is not None and plan.use_pallas(D.shape, D.dtype, copies=4):
        from ..kernels import potrf_inv as _pallas_potrf_inv
        return apply_fault("compute", _pallas_potrf_inv(D, precision, bs=bs))
    return apply_fault("compute", _potrf_inv_impl(D, precision, bs))


def _potrf_inv_impl(D, precision, bs: int = 512):
    """Blocked lower Cholesky of a (w, w) Hermitian block (lower triangle
    valid) returning ``(L, L^{-1})`` with all O(w^3) work as MXU matmuls.

    XLA's native ``cholesky``/``triangular_solve`` at w ~ 2048 are
    latency-bound inner loops (~20 ms / ~12 ms in-graph on v5e); restricting
    them to ``bs``-sized diagonal blocks (~0.9 ms each) and doing the panel
    solve, trailing update, and inverse assembly as matmuls keeps the whole
    diagonal-block factorization near matmul speed.  The explicit inverse is
    what turns every downstream Trsm into a matmul; for blocked factorization
    panels this is the standard GPU/TPU trade (diag-block inverse + GEMM),
    numerically benign at panel sizes since cond(L11) ~ sqrt(cond(A11))."""
    w = D.shape[0]
    dt = D.dtype
    # factor-forming matmuls run at full accumulation (see lu._hi)
    precision = _hi(precision)
    d = jnp.tril(D)
    d = d + jnp.conj(jnp.tril(d, -1)).T
    if w <= bs:
        L = jnp.linalg.cholesky(d)
        Li = lax.linalg.triangular_solve(L, jnp.eye(w, dtype=dt),
                                         left_side=True, lower=True)
        return L, Li
    L = jnp.zeros((w, w), dt)
    Li = jnp.zeros((w, w), dt)
    T = d
    for s in range(0, w, bs):
        e = min(s + bs, w)
        wb = e - s
        dkk = jnp.tril(T[:wb, :wb])
        dkk = dkk + jnp.conj(jnp.tril(dkk, -1)).T
        Lkk = jnp.linalg.cholesky(dkk)
        Likk = lax.linalg.triangular_solve(Lkk, jnp.eye(wb, dtype=dt),
                                           left_side=True, lower=True)
        L = L.at[s:e, s:e].set(Lkk)
        # inverse assembly: Li[s:e, :s] = -Likk @ L[s:e, :s] @ Li[:s, :s]
        if s > 0:
            corr = jnp.matmul(
                Likk, jnp.matmul(L[s:e, :s], Li[:s, :s], precision=precision),
                precision=precision)
            Li = Li.at[s:e, :s].set(-corr.astype(dt))
        Li = Li.at[s:e, s:e].set(Likk)
        if e < w:
            B21 = jnp.matmul(T[wb:, :wb], jnp.conj(Likk).T,
                             precision=precision).astype(dt)
            L = L.at[e:, s:e].set(B21)
            T = T[wb:, wb:] - jnp.matmul(B21, jnp.conj(B21).T,
                                         precision=precision).astype(dt)
    return L, Li


def _local_chol_array(a, n: int, ib: int, precision, lookahead: bool = True,
                      timer=None, plan=None, panels_row_major: bool = False):
    """Blocked lower Cholesky of an (n, n) array (lower triangle valid),
    returning the lower-triangular factor (zeros above the diagonal).
    Shared by the p == 1 driver and the distributed tail crossover (where
    it runs REPLICATED on the gathered trailing block -- deterministic, so
    every device agrees).

    Schedule:
      * diagonal blocks factored by :func:`_potrf_inv` (small-base potrf +
        matmul inverse assembly) and the panel solve L21 = A21 L11^{-H}
        done as a product with the block's inverse over its NON-ZERO
        blocks (``lu._tri_matmul``: ``L11^{-H}`` is upper-triangular and
        the dense product spent half its flops on exact zeros) -- XLA's
        potrf/trsm at nb=2048 are latency-bound;
      * ONE n x n buffer from the first step to the last.  Step k
        addresses its trailing window ``T[o:, o:]`` by static offsets and
        writes its finished panel, zeros above it, into the same buffer's
        columns.  The loop used to copy the shrinking trailing matrix into
        a smaller array at every step (``T = T[w:, w:]``) so that the next
        step could index from zero, and to assemble the kept panels at the
        end: 41.6 GB of HBM traffic for a 4.29 GB operand at N=32768, a
        tenth of the solve (PERF.md 6, PR 34).  Ticks the trace-time
        counter ``chol_update`` once a step;
      * the buffer leaves lower-triangular, so no caller masks the whole
        of it (one more n x n buffer beside this one);
      * the rank-nb update touches only the LOWER triangle, via row-stripe
        blocks ``T[o+i:o+i+q, o:o+i+q] -= L21[i:i+q] L21[:i+q]^H`` (half
        the FLOPs of the full product -- the MXU answer to the reference's
        recursive ``Trrk``);
      * ``lookahead=True`` additionally computes the next panel's column
        strip first and factors diag block k+1 from it, so the
        latency-bound ``_potrf_inv`` inner loop is data-independent of the
        wide remainder stripes and XLA may overlap them (the same pipeline
        as ``lu._local_lu``).  Panel k+1's product WAITS for the stripes
        (an ``optimization_barrier`` with the working buffer): one core
        runs them one after the other anyway, and L21 of step k+1 then
        takes the room L21 of step k leaves.  Left to the scheduler, the
        TPU compiler ran the four block products before the stripes and
        the plan held a panel more (PERF.md 6, PR 47).  ``strip`` and
        ``L21`` stay values of their own: the matmuls never re-read ``T``
        after a write;
      * ``panels_row_major`` (the caller's, on a TPU) pins L21 row-major,
        the layout the TPU compiler gave the panel's one dense matmul
        while the buffer around it is column-major: the update's matmuls
        read it so.  Unpinned, the blocks inherit the buffer's layout, the
        scheduler reorders the loop around them and the plan reads 0.68 GB
        more at N = 32768 (PERF.md 6, PR 47)."""
    tm = timer if timer is not None else NULL_HOOK
    dt = a.dtype
    q = 2 * ib
    T = a
    nxt = None

    def diag_and_panel(step, src, w, below, after=None):
        # diag block ``step`` from src[:w, :w]; its panel solve, a
        # product with the triangular inverse, from the rows under it,
        # once ``after`` (the working buffer, handed back) is computed
        with tm.phase("diag", step) as ph:
            L11, Li11 = _potrf_inv(src[:w, :w], precision, plan=plan)
            ph.done(L11)
        L21 = None
        if below:
            with tm.phase("panel", step) as ph:
                X = src[w:, :w]
                if after is not None:
                    X, after = lax.optimization_barrier((X, after))
                L21 = _tri_matmul(X, jnp.conj(Li11).T, "right",
                                  _hi(precision)).astype(dt)
                if panels_row_major:
                    L21 = _pin_layout(L21, (0, 1))
                ph.done(L21)
        return L11, Li11, L21, after

    if lookahead:
        w0 = min(ib, n)
        *nxt, _ = diag_and_panel(0, T[:, :w0], w0, w0 < n)
    for k, s in enumerate(range(0, n, ib)):
        w = min(ib, n - s)
        o = s + w                   # where the trailing window starts
        if lookahead:
            L11, Li11, L21 = nxt
        else:
            L11, Li11, L21, _ = diag_and_panel(k, T[s:, s:o], w, o < n)
        # the finished panel goes into its own columns with zeros above it,
        # so the buffer leaves the loop lower-triangular and no caller has
        # to mask (and copy) the whole of it.  A plain update-slice: the
        # ``.at[].set`` of a static window goes through a bounds select
        # that the TPU compiler gives a panel-sized value of its own
        with tm.phase("panel", k):
            T = lax.dynamic_update_slice(T, jnp.concatenate(
                [jnp.zeros((s, w), dt), jnp.tril(L11)]
                + ([] if L21 is None else [L21]), axis=0), (0, s))
        if o == n:
            break
        _metrics.inc("chol_update")
        mt = n - o
        if not lookahead:
            with tm.phase("update", k) as ph:
                for i in range(0, mt, q):
                    iq = min(i + q, mt)
                    upd = jnp.matmul(L21[i:iq, :], jnp.conj(L21[:iq, :]).T,
                                     precision=precision)
                    T = T.at[o + i:o + iq, o:o + iq].set(
                        T[o + i:o + iq, o:o + iq] - upd.astype(dt))
                ph.done(T)
            continue
        # look-ahead: the next panel's column strip updates first (one tall
        # narrow matmul), diag block k+1 factors from it; the wide remainder
        # stripes read L21 and their own window of T, never the strip, so
        # the replicated _potrf_inv and the MXU stripes can overlap; panel
        # k+1 solves after them.  The strip is not written back: panel k+1
        # overwrites its columns.
        with tm.phase("update", k):
            w2 = min(ib, mt)
            strip = T[o:, o:o + w2] - jnp.matmul(
                L21, jnp.conj(L21[:w2, :]).T, precision=precision).astype(dt)
        with tm.phase("update", k) as ph:
            for i in range(w2, mt, q):
                iq = min(i + q, mt)
                upd = jnp.matmul(L21[i:iq, :], jnp.conj(L21[w2:iq, :]).T,
                                 precision=precision)
                T = T.at[o + i:o + iq, o + w2:o + iq].set(
                    T[o + i:o + iq, o + w2:o + iq] - upd.astype(dt))
            ph.done(T)
        *nxt, T = diag_and_panel(k + 1, strip, w2, w2 < mt, after=T)
    return T


@partial(jax.jit, static_argnums=1)
def _pin_layout(x, major_to_minor):
    """``x`` held in the given layout where it is an intermediate of a
    compiled program.

    A ``jit`` of its own so that the constraint is always INSIDE a program,
    under whatever transformation the caller runs: inlined into an
    enclosing ``jit``, a small program with default layouts at its boundary
    when called eagerly.  An eager ``with_layout_constraint`` makes an
    executable whose RESULT has the layout, and jax 0.9.0 hands that one
    back from the persistent compile cache without it (transposed data)."""
    return with_layout_constraint(x, Layout(major_to_minor=major_to_minor))


def _pin_column_major(x):
    """``x`` held column-major: the layout the TPU compiler gives the
    one-buffer loop.  Without it the compiler re-lays the whole factor out
    row-major for whoever reads it next (the sweeps of ``hpd_solve``): a
    second n x n buffer beside the working one (PERF.md 6, PR 34)."""
    return _pin_layout(x, (1, 0))


def _pins_layouts(grid) -> bool:
    """The layouts this module pins were read from the TPU compiler;
    elsewhere nothing asks for them.  Under ``jax.disable_jit()`` the pin's
    own jit would run it eagerly."""
    return (grid.devices[0].platform == "tpu"
            and not jax.config.jax_disable_jit)


def _local_cholesky(A: DistMatrix, nb: int | None, precision,
                    lookahead: bool = True, timer=None,
                    plan=None) -> DistMatrix:
    """Sequential (p == 1) lower path: the analog of the reference's local
    ``Matrix<T>`` dispatch onto sequential BLAS.  On a 1x1 grid the storage
    array IS the global matrix, so the whole blocked loop is one fused XLA
    program with no shard_map/redistribute sub-computation boundaries."""
    ib = max(nb or 2048, 1)
    on_tpu = _pins_layouts(A.grid)
    out = _local_chol_array(A.local, A.gshape[0], ib, precision,
                            lookahead=lookahead, timer=timer, plan=plan,
                            panels_row_major=on_tpu)
    if on_tpu:
        out = _pin_column_major(out)
    return A.with_local(out)


@_scoped("el.cholesky")
def cholesky(A: DistMatrix, uplo: str = "L", nb: int | str | None = None,
             precision=None, lookahead: bool | str = True,
             crossover: int | str | None = None,
             panel_impl: str | None = None,
             comm_precision: str | None = None,
             redist_path: str | None = None, timer=None,
             health=None, abft=None) -> DistMatrix:
    """Cholesky factor of an HPD [MC,MR] matrix; reads only the ``uplo``
    triangle.  Returns L (A = L L^H) for 'L', U (A = U^H U) for 'U'.

    ``lookahead`` selects the pipelined schedule (module docstring; ``False``
    restores the classic right-looking order, the same factor);
    ``crossover`` is the trailing-matrix size at which the distributed loop
    gathers the tail once and finishes locally (``None`` = :data:`_CROSSOVER`
    with look-ahead, disabled classic; 0 never crosses over); ``timer``
    enables eager per-phase wall-clock attribution
    (``elemental_tpu.obs.PhaseTimer``).

    ``panel_impl`` (``None`` | ``'xla'`` | ``'pallas'`` | ``'auto'``)
    selects the diagonal-block factor/inverse IMPLEMENTATION: ``'pallas'``
    runs :func:`_potrf_inv` as ONE fused VMEM-resident kernel
    (``kernels.potrf_inv``: blocked potrf + triangular inverse in a
    single launch; ``interpret=True`` off-TPU), ``None``/``'xla'`` keep
    the blocked XLA path.  Residual-bounded twin (same math, different
    scalar-recurrence rounding -- pinned by ``tests/kernels``); complex
    dtypes and oversize blocks fall back to XLA silently.  The schedule
    and every collective are IDENTICAL under either value (comm-plan
    goldens byte-pinned by ``tools/check.sh kernels``).

    ``comm_precision`` (``None`` | ``'bf16'`` | ``'int8'``) selects the
    WIRE precision of the schedule's redistributions -- the diagonal-block
    gathers, the [VC,STAR] panel moves, the fused ``panel_spread`` and
    the crossover tail gather all encode narrow, move 2-4x fewer bytes
    at identical round counts, and decode back before any local math
    (see ``redist.quantize``).  Opt-in: ``None`` (default) is
    bit-identical; quantized wire raises the factor residual to the
    ~1e-2..1e-3 relative level -- pair with
    ``resilience.certified_solve('hpd', ...)`` for certified answers.

    ``redist_path`` (``None`` | ``'chain'`` | ``'direct'`` | ``'auto'``)
    selects the redistribution ROUTE of the same sites: ``'direct'``
    compiles each dist change into a one-shot collective plan
    (``redist.plan``), ``'auto'`` arbitrates per move via the engine's
    chain-vs-plan cost mirror, and ``None``/``'chain'`` keep the factored
    multi-hop chain (bit-identical baseline).  Both routes move the same
    values, so the factor is unchanged up to collective reduction order.

    Any of ``nb`` / ``lookahead`` / ``crossover`` / ``comm_precision`` /
    ``redist_path`` may be ``'auto'``: the tuning subsystem resolves them
    per (shape, dtype, grid, backend) -- measured-cache winner first,
    analytic cost model cold (explicit values always win; see
    ``elemental_tpu/tune``).

    ``health`` opts into the resilience guards (NaN/Inf scans, growth
    estimate, non-positive/near-zero diagonal detection on the ``diag``
    ticks): a ``HealthMonitor`` or ``True``, same semantics as
    ``lu(..., health=...)``; ``None`` (default) attaches nothing.

    ``abft`` opts into checksum-guarded execution with panel-granular
    recovery (same semantics as ``lu(..., abft=...)``; ISSUE 11): the
    guarded path verifies column-sum invariants per panel and on
    violation re-executes only that panel step.  It forces the classic
    right-looking schedule (``lookahead`` / ``crossover`` ignored);
    ``abft=None`` (default) is the unguarded path, bit-identical to
    before.
    """
    _check_mcmr(A)
    precision = _hi(precision)
    if any(isinstance(v, str) for v in (nb, lookahead, crossover)) \
            or comm_precision == "auto" or redist_path == "auto" \
            or panel_impl == "auto":
        from ..tune.policy import resolve_knobs
        kn = resolve_knobs("cholesky", gshape=A.gshape, dtype=A.dtype,
                           grid=A.grid, knobs={"nb": nb, "lookahead": lookahead,
                                               "crossover": crossover,
                                               "panel_impl": panel_impl,
                                               "comm_precision": comm_precision,
                                               "redist_path": redist_path})
        nb, lookahead, crossover = kn["nb"], kn["lookahead"], kn["crossover"]
        comm_precision = kn["comm_precision"]
        redist_path = kn["redist_path"]
        panel_impl = kn["panel_impl"]
    check_comm_precision(comm_precision)
    rp = redist_path
    from ..kernels import resolve_panel
    plan = resolve_panel(panel_impl, dtype=A.dtype)
    if uplo.upper().startswith("U"):
        # U = (lower factor of A^H-as-lower)^H; A hermitian so the data of
        # the upper triangle, conj-transposed, is the lower triangle.
        Alow = redistribute(transpose_dist(A, conj=True), MC, MR)
        L = cholesky(Alow, "L", nb=nb, precision=precision,
                     lookahead=lookahead, crossover=crossover,
                     panel_impl=panel_impl,
                     comm_precision=comm_precision, redist_path=redist_path,
                     timer=timer, health=health, abft=abft)
        return redistribute(transpose_dist(L, conj=True), MC, MR)
    if abft:
        from ..resilience.abft import abft_cholesky
        return abft_cholesky(A, nb=nb, precision=precision,
                             comm_precision=comm_precision, timer=timer,
                             health=health, abft=abft, plan=plan)

    m = A.gshape[0]
    if A.gshape != (m, m):
        raise ValueError(f"cholesky needs square, got {A.gshape}")
    g = A.grid
    tm = _phase_hook("cholesky", timer)
    hm = None
    if health:
        from ..resilience.health import attach_health
        tm, hm = attach_health("cholesky", health, tm, scale_from=A)
    tm.start()
    if g.size == 1:
        out = _local_cholesky(A, nb, precision, lookahead, tm, plan)
        if hm is not None:
            hm.report()
        return out
    r, c = g.height, g.width
    ib = _blocksize(nb, math.lcm(r, c), m)
    xover = (_CROSSOVER if lookahead else 0) if crossover is None \
        else max(int(crossover), 0)
    cp = comm_precision
    # The factor is built in ONE copy of the operand, and the mask that
    # leaves zeros above the diagonal is part of making that copy: no step
    # writes above the diagonal (every update is masked to the lower
    # triangle, the diagonal blocks go in lower-triangular, the tail leaves
    # _local_chol_array so) and none reads there (_potrf_inv takes jnp.tril
    # of its block).  Masked at the EXIT instead, the select was one more
    # whole shard beside the working one: at N = 65536 on 2x2 the 4.29 GB
    # by which the program missed the chip (PERF.md 6, PR 35).
    with tm.phase("mask", 0):
        L = make_trapezoidal(A, "L")

    panels_row_major = _pins_layouts(g)

    def factor_diag(step, src, lo, hi):
        # replicated diagonal-block factor + inverse: every device runs
        # the same deterministic _potrf_inv, so the panel Trsm is a matmul
        with tm.phase("diag", step) as ph:
            A11 = redistribute(view(src, rows=(lo, hi), cols=(lo, hi)),
                               STAR, STAR, comm_precision=cp, path=rp)
            L11, Li11 = _potrf_inv(A11.local, precision, plan=plan)
            ph.done(L11)
        return L11, Li11

    def solve_panel(step, src, rows, cols, Li11):
        # L21 = A21 L11^{-H} on the [VC,STAR] panel, pinned row-major on
        # a TPU as in _local_chol_array: the compiler gave the one dense
        # matmul that layout and the panel spread reads it so; the block
        # products come column-major, and the spread then re-laid every
        # panel (0.6 ms a step at N = 32768 on 2x2, more than the products
        # save: PERF.md 6, PR 47)
        with tm.phase("panel", step) as ph:
            A21_vc = redistribute(view(src, rows=rows, cols=cols), VC, STAR,
                                  comm_precision=cp, path=rp)
            x21 = _tri_matmul(A21_vc.local, jnp.conj(Li11).T, "right",
                              _hi(precision)).astype(A.dtype)
            if panels_row_major:
                x21 = _pin_layout(x21, (0, 1))
            L21_vc = DistMatrix(x21, (rows[1] - rows[0], cols[1] - cols[0]),
                                VC, STAR, 0, 0, g)
            ph.done(L21_vc)
        return L21_vc

    def minus_lower(W, L21, L21H, below=0):
        # W - L21 L21H on the matrix's lower triangle, W's own values
        # elsewhere; W's first row lies ``below`` rows under the diagonal
        # entry of its first column.  The mask is on the product, not on the
        # difference: ``w - where(m, upd, 0)`` reads its window of L inside
        # the matmul's fusion and writes it in place, where
        # ``where(m, w - upd, w)`` had the compiler slice the window out
        # first, most of a shard at step 0.  The same numbers: w - 0 is w
        I, J = _global_indices(W)
        upd = jnp.matmul(L21.local, L21H.local, precision=precision)
        return W.with_local(W.local - jnp.where(
            J[None, :] <= I[:, None] + below, upd.astype(A.dtype), 0))

    def update_stripes(L, e, w2, L21, L21H):
        # The trailing window (origin e) right of its column w2, minus
        # L21 L21H, by COLUMN stripes of the lower trapezoid, q = 2 ib wide
        # (_local_chol_array's q):
        #   L[e+j:, e+j:e+jq] -= L21[j:] L21H[:, j:jq]
        # One product over the square window, its upper half masked away,
        # was half the update's flops thrown away at the matmuls' roofline
        # (PERF.md 6, PR 36).  Stripe ends are multiples of ib, so of
        # lcm(r, c): every stripe is a static local window, the same share
        # of it on every device, written where it was read; what lies above
        # a stripe's diagonal block is neither read nor written.
        # Columns and not rows (_local_chol_array's stripes), and the corner
        # stripe, which is its own diagonal block, starts one block row
        # higher: every product here is TALLER than wide.  One that is not
        # has the TPU compiler lay the whole working shard out row-major,
        # and the plan then holds one shard more (1.144 GB for 0.845 at
        # N = 16384 on 2x2; PERF.md 6, PR 36).  The block above the corner
        # is masked away: one or two block products a step, 0.8 % at N = 65536
        mt, q = m - e, 2 * ib
        for j in range(w2, mt, q):
            jq = min(j + q, mt)
            i = max(j - ib, 0) if jq == mt else j
            rows, cols = (e + i, m), (e + j, e + jq)
            _metrics.inc("chol_update_stripe")
            L = update_view(
                L, minus_lower(view(L, rows=rows, cols=cols),
                               view(L21, rows=(i, mt)),
                               view(L21H, cols=(j, jq)), i - j),
                rows=rows, cols=cols)
        return L

    if lookahead:
        # prologue: factor diag block 0 + solve panel 0 from the input
        e0 = min(ib, m)
        L11, Li11 = factor_diag(0, L, 0, e0)
        L21_vc = solve_panel(0, L, (e0, m), (0, e0), Li11) if e0 < m \
            else None
        nxt = (L11, Li11, L21_vc)
    for k, s in enumerate(range(0, m, ib)):
        e = min(s + ib, m)
        if lookahead:
            L11, Li11, L21_vc = nxt
        else:
            L11, Li11 = factor_diag(k, L, s, e)
        with tm.phase("diag", k):
            L11_ss = DistMatrix(jnp.tril(L11), (e - s, e - s), STAR, STAR,
                                0, 0, g)
            L = update_view(L, redistribute(L11_ss, MC, MR), rows=(s, e),
                            cols=(s, e))
        if e == m:
            break
        _metrics.inc("chol_update")
        if not lookahead:
            L21_vc = solve_panel(k, L, (e, m), (s, e), Li11)
        with tm.phase("spread", k) as ph:
            L21_mc, L21H_mr = panel_spread(L21_vc, conj=True,
                                           comm_precision=cp)
            ph.done(L21_mc, L21H_mr)
        tail = bool(xover) and m - e <= xover
        if not lookahead:
            with tm.phase("update", k) as ph:
                L = update_stripes(L, e, 0, L21_mc, L21H_mr)
                L = update_view(L, redistribute(L21_mc, MC, MR), rows=(e, m),
                                cols=(s, e))
                ph.done(L)
        else:
            # Every write goes into L where its operand was just read, so
            # the compiler updates the one working shard in place: panel k's
            # columns, then (a), then (b), each a read-modify-write of its
            # own window.  Computed from one captured L and written back
            # together at the end, the three windows left readers of the
            # old shard unordered against its writers, and the compiler
            # answered with a copy of the whole shard at every step: a
            # second working shard in the plan, and 4.29 GB of copying a
            # step at N = 65536 (PERF.md 6, PR 35).
            with tm.phase("update", k):
                L = update_view(L, redistribute(L21_mc, MC, MR), rows=(e, m),
                                cols=(s, e))
                # (a) narrow strip update: the next panel's columns of A22,
                # one tall matmul (only its top block meets the diagonal)
                e2 = min(e + ib, m)
                stripD = minus_lower(view(L, rows=(e, m), cols=(e, e2)),
                                     L21_mc, view(L21H_mr, cols=(0, e2 - e)))
                L = update_view(L, stripD, rows=(e, m), cols=(e, e2))
            if not tail:
                # factor diag block k+1 + solve panel k+1 from the strip (the
                # value, not L), off the critical path of the wide remainder
                # update, which reads neither result
                L11n, Li11n = factor_diag(k + 1, stripD, 0, e2 - e)
                L21n_vc = solve_panel(k + 1, stripD, (e2 - e, m - e),
                                      (0, e2 - e), Li11n) if e2 < m else None
                nxt = (L11n, Li11n, L21n_vc)
            # (b) wide remainder update
            with tm.phase("update", k) as ph:
                L = update_stripes(L, e, e2 - e, L21_mc, L21H_mr)
                ph.done(L)
        if tail:
            # crossover-to-local: one gather of the (fully updated) trailing
            # block, replicated sequential finish, one scatter back -- the
            # remaining t/nb steps of per-step collective latency collapse
            # into a single round trip
            with tm.phase("tail", k) as ph:
                Atail = redistribute(view(L, rows=(e, m), cols=(e, m)),
                                     STAR, STAR, comm_precision=cp, path=rp)
                lt = _local_chol_array(Atail.local, m - e, ib, precision,
                                       lookahead=lookahead, plan=plan)
                Lt_ss = DistMatrix(lt, (m - e, m - e), STAR, STAR, 0, 0, g)
                L = update_view(L, redistribute(Lt_ss, MC, MR),
                                rows=(e, m), cols=(e, m))
                ph.done(L)
            break
    if hm is not None:
        hm.report()
    return L


@_scoped("el.hpd_solve")
def hpd_solve(A: DistMatrix, B: DistMatrix, uplo: str = "L",
              nb: int | None = None, precision=None, info: bool = False,
              health=None):
    """Solve A X = B for HPD A: Cholesky + forward/backward sweeps
    (``El::HPDSolve``, ``src/lapack_like/solve/HPDSolve.cpp``).

    ``info=True`` returns ``(X, info)`` with the structured singularity
    signal ``{"singular", "diag_index", "finite"}`` from the factor's
    diagonal (a singular / non-PD A surfaces as a non-finite or
    non-positive diagonal entry instead of a silently NaN X; eager-mode
    only); ``health`` forwards to :func:`cholesky`.  For the
    residual-certified path use
    ``elemental_tpu.resilience.certified_solve('hpd', A, B)``."""
    uplo = "U" if uplo.upper().startswith("U") else "L"
    with jax.named_scope("factor"):
        F = cholesky(A, uplo, nb=nb, precision=precision, health=health)
    with jax.named_scope("sweeps"):
        X = cholesky_solve_after(F, B, uplo, nb=nb, precision=precision)
    if not info:
        return X
    from ..resilience.health import factor_diag_info
    return X, factor_diag_info("hpd", F)


def cholesky_solve_after(L: DistMatrix, B: DistMatrix, uplo: str = "L",
                         nb: int | None = None, precision=None) -> DistMatrix:
    """Re-use an existing factor (``cholesky::SolveAfter``)."""
    precision = _hi(precision)
    if uplo.upper().startswith("U"):
        Y = trsm("L", "U", "C", L, B, nb=nb, precision=precision)
        return trsm("L", "U", "N", L, Y, nb=nb, precision=precision)
    Y = trsm("L", "L", "N", L, B, nb=nb, precision=precision)
    return trsm("L", "L", "C", L, Y, nb=nb, precision=precision)


def cholesky_pivoted(A: DistMatrix, tol: float = 0.0, precision=None):
    """Full (diagonal) pivoted Cholesky of a PSD matrix:
    ``P A P^T = L L^H`` with the pivot chosen as the largest remaining
    diagonal each step (LAPACK ``pstrf`` / ``cholesky::PivotedLVar3``,
    Elemental ``src/lapack_like/factor/Cholesky/PivotedLVar3.hpp``).

    Returns ``(L, perm, rank)``: L lower-triangular [MC,MR], ``perm`` the
    traced permutation (``(P A P^T)[i, j] = A[perm[i], perm[j]]``), and
    the detected numerical rank (columns whose pivot fell below
    ``tol * max_diag`` are zeroed).

    The factorization runs REPLICATED on the gathered matrix (one jitted
    fori_loop; the reference's pivoted variant is likewise its slow
    path -- per-column pivot search serializes everything) and scatters
    the factor back; use the unpivoted :func:`cholesky` for speed on
    definite matrices.
    """
    _check_mcmr(A)
    n = A.gshape[0]
    if A.gshape != (n, n):
        raise ValueError(f"cholesky_pivoted needs square, got {A.gshape}")
    g = A.grid
    Ag = redistribute(A, STAR, STAR).local
    a = jnp.tril(Ag)
    a = a + jnp.conj(jnp.tril(a, -1)).T
    rdt = jnp.real(a).dtype
    # rank threshold anchored on A's ORIGINAL diagonal scale (pstrf
    # semantics); the working diagonal mixes in L's sqrt-scaled entries
    thresh = jnp.asarray(tol, rdt) * jnp.maximum(
        jnp.max(jnp.real(jnp.diagonal(a))), jnp.asarray(1e-30, rdt))

    def body(j, state):
        a, perm, rank = state
        d = jnp.real(jnp.diagonal(a))
        idx = jnp.arange(n)
        cand = jnp.where(idx >= j, d, -jnp.inf)
        p = jnp.argmax(cand)
        # symmetric swap rows/cols j <-> p
        rj, rp = a[j], a[p]
        a = a.at[j].set(rp).at[p].set(rj)
        cj, cp = a[:, j], a[:, p]
        a = a.at[:, j].set(cp).at[:, p].set(cj)
        perm = perm.at[j].set(perm[p]).at[p].set(perm[j])
        piv = jnp.real(a[j, j])
        ok = piv > thresh
        sq = jnp.sqrt(jnp.where(ok, piv, 1.0))
        col = jnp.where(idx > j, a[:, j] / sq, 0).at[j].set(sq)
        col = jnp.where(ok, col, 0)
        # trailing update: a[j+1:, j+1:] -= col col^H (lower part suffices)
        mask = (idx[:, None] > j) & (idx[None, :] > j)
        a = jnp.where(mask, a - jnp.outer(col, jnp.conj(col)), a)
        a = a.at[:, j].set(col)
        rank = rank + jnp.where(ok, 1, 0)
        return a, perm, rank

    a, perm, rank = lax.fori_loop(0, n, body, (a, jnp.arange(n), 0))
    L = jnp.tril(a)
    Ld = redistribute(DistMatrix(L.astype(A.dtype), (n, n), STAR, STAR,
                                 0, 0, g), MC, MR)
    return Ld, perm, rank


def cholesky_mod(L: DistMatrix, V: DistMatrix, alpha: float = 1.0,
                 precision=None):
    """Rank-k Cholesky modification (``El::CholeskyMod``,
    ``Cholesky/{LMod,UMod}.hpp``): given lower L with A = L L^H, return
    the factor of ``A + alpha V V^H`` in O(n^2 k) via the classic
    column-recurrence (one hyperbolic/Givens sweep per update vector).

    ``alpha < 0`` is a DOWNDATE and requires the result to stay positive
    definite (the sweep's r^2 staying positive); like the pivoted
    variants, the sweep runs replicated on the gathered factor (it is a
    latency-bound sequential recurrence -- the reference's is too) and
    scatters back."""
    _check_mcmr(L, V)
    if jnp.issubdtype(L.dtype, jnp.complexfloating):
        raise NotImplementedError("cholesky_mod supports real factors")
    n = L.gshape[0]
    if V.gshape[0] != n:
        raise ValueError(f"V rows {V.gshape[0]} != n {n}")
    k = V.gshape[1]
    g = L.grid
    a = jnp.tril(redistribute(L, STAR, STAR).local)
    W = redistribute(V, STAR, STAR).local.astype(a.dtype)
    sign = 1.0 if alpha >= 0 else -1.0
    scal = math.sqrt(abs(alpha))
    idx = jnp.arange(n)

    def one_vector(a, w):
        def body(j, state):
            a, w = state
            ljj = a[j, j]
            wj = w[j]
            # an indefinite downdate makes r2 negative: sqrt -> NaN, which
            # poisons the factor and is caught by the host check below
            r = jnp.sqrt(ljj * ljj + sign * wj * wj)
            c = r / ljj
            s = wj / ljj
            col = a[:, j]
            newcol = (col + sign * s * w) / c
            newcol = jnp.where(idx > j, newcol, col).at[j].set(r)
            wnew = jnp.where(idx > j, c * w - s * newcol, w)
            return a.at[:, j].set(newcol), wnew

        a, _ = lax.fori_loop(0, n, body, (a, w))
        return a

    for t in range(k):
        a = one_vector(a, scal * W[:, t])
    import numpy as _np
    if not bool(_np.isfinite(_np.asarray(jnp.diagonal(a))).all()):
        raise ValueError("cholesky_mod: downdate leaves the matrix "
                         "indefinite (El::CholeskyMod throws here too)")
    out = redistribute(DistMatrix(jnp.tril(a), (n, n), STAR, STAR, 0, 0, g),
                       MC, MR)
    return out
