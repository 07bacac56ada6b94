"""Householder QR, compact-WY application, TSQR, least squares.

Reference: Elemental ``src/lapack_like/factor/QR.cpp`` +
``QR/{Householder,PanelHouseholder,TS,ApplyQ,SolveAfter}.hpp`` and
``src/lapack_like/reflect/ApplyPacked`` -- BASELINE.json's
"Householder QR / least-squares (TSQR panel factor)" config.

TPU-first design (same pattern as lu.py): the panel is gathered to
[STAR,STAR] and reduced REDUNDANTLY on every device with a local larfg
fori_loop (the reference's ``qr::PanelHouseholder`` runs one Nrm2 AllReduce
per column).  The trailing update is the compact-WY form
``A2 -= V T^H (V^H A2)`` where ``V^H A2`` is a storage matmul whose
mc-sharded contraction GSPMD lowers to local MXU product + psum -- exactly
the reference's [MC,STAR]/[STAR,MR] Her2k-style update, with T computed
locally (larft) on the replicated panel.

Packing follows LAPACK geqrf: R on/above the diagonal, the Householder
vectors' tails below it (unit diagonal implicit), plus a tau vector.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec

from ..core.dist import MC, MR, VC, STAR, rank_of
from ..core.distmatrix import DistMatrix
from ..core.view import view, update_view
from ..core.compat import shard_map
from ..redist.engine import apply_fault, redistribute
from ..redist.interior import interior_view, vstack, _blank
from ..blas.level1 import shift_diagonal
from ..blas.level3 import _blocksize, _check_mcmr, trsm
from ..obs import metrics as _metrics
from .lu import (_update_cols_lt, _update_cols_ge, _hi, _phase_hook,
                 _nopiv_panel, _scoped)


# ---------------------------------------------------------------------
# replicated panel reduction (larfg loop) + larft
# ---------------------------------------------------------------------

def _panel_qr(P):
    """Unblocked Householder QR of a replicated (M, k) panel.

    Returns (packed V\\R panel, tau).  LAPACK larfg conventions: real beta,
    H_j = I - tau_j v_j v_j^H, applied as H^H during the reduction, so the
    panel ends as Q^H A with Q = H_0 ... H_{k-1}."""
    M, k = P.shape
    ridx = jnp.arange(M)
    cidx = jnp.arange(k)

    def body(j, state):
        P, tau = state
        col = P[:, j]
        alpha = col[j]
        tail = jnp.where(ridx > j, col, 0)
        sigma = jnp.sum(jnp.abs(tail) ** 2)
        anorm = jnp.sqrt(jnp.abs(alpha) ** 2 + sigma)
        re_a = jnp.real(alpha)
        beta = -jnp.sign(jnp.where(re_a == 0, 1.0, re_a)) * anorm   # real
        degenerate = anorm == 0
        safe_beta = jnp.where(degenerate, 1.0, beta)
        tau_j = jnp.where(degenerate, 0.0, (safe_beta - alpha) / safe_beta)
        denom = alpha - safe_beta
        safe_denom = jnp.where(denom == 0, 1.0, denom)
        v = jnp.where(ridx > j, col / safe_denom, 0)
        v = v.at[j].set(jnp.where(degenerate, 0.0, 1.0).astype(P.dtype))
        # apply H_j^H = I - conj(tau) v v^H to the trailing columns.
        # HIGHEST precision: on TPU the default lowers dots to bf16, which
        # would corrupt the reflectors themselves (panel work is tiny).
        w = jnp.matmul(jnp.conj(v), P, precision=lax.Precision.HIGHEST)
        upd = jnp.outer(jnp.conj(tau_j) * v, w)
        P = P - jnp.where(cidx[None, :] > j, upd, 0)
        # store [beta; v-tail] in column j
        newcol = jnp.where(ridx > j, v, P[:, j]).at[j].set(
            jnp.asarray(beta, P.dtype))
        newcol = jnp.where(ridx >= j, newcol, P[:, j])
        P = P.at[:, j].set(newcol)
        tau = tau.at[j].set(jnp.asarray(tau_j, tau.dtype))
        return P, tau

    tau0 = jnp.zeros((k,), P.dtype)
    return lax.fori_loop(0, k, body, (P, tau0))


def _larft(V, tau):
    """Forward-columnwise block-reflector triangle: Q = I - V T V^H."""
    k = tau.shape[0]
    B = jnp.matmul(jnp.conj(V).T, V, precision=lax.Precision.HIGHEST)
    kidx = jnp.arange(k)

    def body(i, T):
        col = jnp.where(kidx < i, B[:, i], 0)
        newcol = -tau[i] * jnp.matmul(T, col, precision=lax.Precision.HIGHEST)
        newcol = newcol.at[i].set(tau[i])
        return T.at[:, i].set(newcol)

    return lax.fori_loop(0, k, body, jnp.zeros((k, k), V.dtype))


def _panel_v(Pf):
    """Unit-lower V from a packed panel (replicated)."""
    M, k = Pf.shape
    return jnp.tril(Pf, -1) + jnp.eye(M, k, dtype=Pf.dtype)


def _panel_qr_dispatch(P, plan=None):
    """Route one classic replicated panel through the resolved
    ``panel_impl`` plan: returns ``(packed, tau, T)`` with ``T`` the
    fused kernel's larft triangle when the Pallas path ran, else
    ``None`` (the caller builds T via :func:`_larft` exactly as
    before).  ``plan=None`` / complex / oversize panels keep the XLA
    larfg recurrence -- the status-quo path, bit-identical."""
    if plan is not None and plan.use_pallas(P.shape, P.dtype, copies=4):
        from ..kernels import qr_panel
        return qr_panel(P)
    Pf, tau = _panel_qr(P)
    return Pf, tau, None


# ---------------------------------------------------------------------
# TSQR/CAQR tree panel (the QR rider of the CALU PR): local Householder
# QR per grid-row slab, a log-depth pairwise reduction of the R factors,
# and the aggregated thin Q converted BACK to geqrf packing via the
# LU-based Householder reconstruction (Ballard/Demmel et al., "Recon-
# structing Householder vectors from TSQR"), so every downstream consumer
# -- compact-WY trailing updates, apply_q, least_squares -- is unchanged.
# ---------------------------------------------------------------------

def _tsqr_tree(P, r: int, precision=None):
    """Replicated TSQR reduction of an (M, b) panel over ``r`` cyclic
    grid-row slabs: returns ``(Q1, R)`` with Q1 the explicit thin
    orthonormal factor (rows back in original order) and R upper
    triangular.  The tree mirrors a message-passing CAQR: slab QRs are
    independent (zero communication), then ceil(log2(r)) pairwise
    stacked-QR playoffs combine the R factors, with each leaf's b x b
    aggregated transform accumulated so Q1 is assembled by one matmul
    per slab."""
    M, b = P.shape
    lslab = max(-(-M // r), b)
    sidx = jnp.arange(lslab)[None, :] * r + jnp.arange(r)[:, None]
    ok = sidx < M                                       # (r, lslab)
    vals = jnp.where(ok[:, :, None], P[jnp.clip(sidx, 0, M - 1)], 0)
    with jax.default_matmul_precision("highest"):
        Qs, Rs = jax.vmap(lambda v: jnp.linalg.qr(v, mode="reduced"))(vals)
    Rlist = [Rs[i] for i in range(r)]
    groups = [[i] for i in range(r)]
    Ts = [None] * r                                     # None == identity
    while len(Rlist) > 1:
        nR, nG = [], []
        for a in range(0, len(Rlist) - 1, 2):
            st = jnp.concatenate([Rlist[a], Rlist[a + 1]], axis=0)
            with jax.default_matmul_precision("highest"):
                q, rnew = jnp.linalg.qr(st, mode="reduced")
            for leaf, blk in ((groups[a], q[:b]), (groups[a + 1], q[b:])):
                for i in leaf:
                    Ts[i] = blk if Ts[i] is None else jnp.matmul(
                        Ts[i], blk, precision=_hi(precision))
            nR.append(rnew)
            nG.append(groups[a] + groups[a + 1])
        if len(Rlist) % 2:
            nR.append(Rlist[-1])
            nG.append(groups[-1])
        Rlist, groups = nR, nG
    T = jnp.stack([jnp.eye(b, dtype=P.dtype) if t is None else t
                   for t in Ts])
    Qfull = jnp.matmul(Qs, T, precision=_hi(precision))  # (r, lslab, b)
    targets = jnp.where(ok, sidx, M).reshape(-1)
    Q1 = jnp.zeros((M, b), P.dtype).at[targets].set(
        Qfull.reshape(r * lslab, b), mode="drop")
    return Q1, Rlist[0]


def _panel_qr_tsqr(P, r: int, precision=None):
    """TSQR tree panel in geqrf packing: ``(packed V\\R, tau)``, same
    contract as :func:`_panel_qr`.

    The tree (:func:`_tsqr_tree`) produces the explicit thin ``Q1`` and
    ``R``; the Householder form is reconstructed exactly from the
    identity ``Q1 - [I; 0] = Y U`` (Y the unit-lower-trapezoidal
    reflector panel, ``U = -T Y1^H`` upper triangular), i.e. ONE
    unpivoted LU of ``Q1 - I`` -- the lu module's :func:`_nopiv_panel` --
    with ``tau_j = -U[j,j]``.  Columns are sign-flipped first so the
    diagonal of ``Q1 - I`` is bounded away from zero (the stability
    device of the reconstruction paper).  Replaces the serial
    column-at-a-time larfg recurrence over the full panel height with
    slab-local QR kernels plus log-depth b x b reductions."""
    M, b = P.shape
    Q1, R = _tsqr_tree(P, max(int(r), 1), precision)
    d = jnp.diagonal(Q1[:b])
    absd = jnp.abs(d)
    s = jnp.where(absd == 0, -jnp.ones_like(d),
                  -(jnp.conj(d) / jnp.where(absd == 0, 1, absd)))
    s = s.astype(P.dtype)
    Q1p = Q1 * s[None, :]
    Rp = jnp.conj(s)[:, None] * R
    B = Q1p.at[:b].add(-jnp.eye(b, dtype=P.dtype))
    F = _nopiv_panel(B, b, precision)
    tau = -jnp.diagonal(F[:b])
    packed = jnp.concatenate(
        [jnp.triu(Rp) + jnp.tril(F[:b], -1), F[b:]], axis=0)
    return packed, tau


def _wy_apply(B: DistMatrix, V, Tm, rows, cols, precision) -> DistMatrix:
    """The view ``B[rows, cols]`` less ``V Tm V^H`` times itself: one block
    reflector in compact-WY form, V (the view's rows, k) and ``Tm``
    replicated.  ``V^H B2`` is a storage matmul whose mc-sharded
    contraction lands [STAR,MR]."""
    V_ss = DistMatrix(V, V.shape, STAR, STAR, 0, 0, B.grid)
    V_mc = redistribute(V_ss, MC, STAR)
    B2 = view(B, rows=rows, cols=cols)
    W = jnp.matmul(jnp.conj(V_mc.local).T, B2.local, precision=_hi(precision))
    W = jnp.matmul(Tm, W, precision=_hi(precision))
    upd = jnp.matmul(V_mc.local, W, precision=_hi(precision))
    return B2.with_local(B2.local - upd.astype(B.dtype))


# ---------------------------------------------------------------------
# blocked Householder QR
# ---------------------------------------------------------------------

@_scoped("el.qr")
def qr(A: DistMatrix, nb: int | str | None = None, precision=None,
       panel: str = "classic", panel_impl: str | None = None,
       comm_precision: str | None = None,
       timer=None, health=None, redist_path: str | None = None,
       abft=None):
    """Blocked Householder QR; returns (packed, tau) in geqrf format.

    ``nb='auto'`` asks the tuning subsystem for the panel width.  The
    resolved block size is ATTACHED to the returned packed matrix (the
    ``_qr_nb`` attribute), so :func:`apply_q` called with ``nb=None``
    reuses exactly the factorization's blocking and a mismatching
    explicit ``nb`` raises instead of silently producing a wrong Q.  (The
    attribute is host-side metadata: it does not survive a ``jax.jit``
    boundary -- inside jit, pass the same ``nb`` to both ends as before.)
    ``timer`` enables eager per-phase (panel/update) wall-clock
    attribution, same protocol as ``lu``/``cholesky`` (ISSUE 5).

    ``panel`` selects the panel reduction: ``'classic'`` (default) is the
    replicated column-at-a-time larfg recurrence; ``'tsqr'`` the TSQR/CAQR
    tree panel (:func:`_panel_qr_tsqr`) -- slab-local QR kernels per grid
    row, a log-depth R reduction, and LU-based Householder reconstruction
    back into the SAME geqrf packing, so ``apply_q``/``least_squares``
    consume the result unchanged (R's diagonal signs may differ from
    classic; the (packed, tau) pair is self-consistent).  ``'auto'``
    resolves through the tuning subsystem like ``nb``.

    ``panel_impl`` (``None`` | ``'xla'`` | ``'pallas'`` | ``'auto'``)
    selects the classic panel's IMPLEMENTATION, orthogonal to ``panel``:
    ``'pallas'`` fuses the whole larfg reflector chain AND the larft
    T-triangle build into ONE VMEM-resident kernel
    (``kernels.qr_panel``; ``interpret=True`` off-TPU), so the driver
    skips the separate ``_larft`` launch per step.  Residual-bounded
    twin of the XLA recurrence (pinned by ``tests/kernels``); complex
    dtypes and oversize panels fall back to XLA silently; the TSQR tree
    panel keeps its slab kernels.  The schedule and every collective
    are identical under either value (comm-plan goldens byte-pinned).

    ``comm_precision`` (``None`` | ``'bf16'`` | ``'int8'`` | ``'auto'``)
    selects the wire precision of the per-step panel gathers (the
    sweep's only bulk collective): narrow encode -> gather -> decode, so
    the gathers move 2-4x fewer bytes at identical round counts.
    Opt-in; ``None`` (default) is bit-identical.  See the README's
    "Quantized collectives" section for the accuracy trade.

    ``redist_path`` (``None`` | ``'chain'`` | ``'direct'`` | ``'auto'``)
    routes the panel gathers through the one-shot plan compiler instead
    of the hop chain; ``'auto'`` arbitrates per move with the measured
    redist constants when recorded (see :mod:`perf.redist_bench`).

    ``health`` opts into the resilience subsystem's numerical-health
    guards, with the same contract as ``lu``/``cholesky`` (ISSUE 7 gap
    closed in ISSUE 9): pass a ``HealthMonitor`` (read
    ``monitor.report()`` afterwards) or ``True`` (report retrievable via
    ``resilience.last_health_report('qr')``).  Every panel/update tick is
    NaN/Inf-scanned and growth-tracked, and the packed panel's diagonal
    -- which carries R's diagonal (the larfg betas) -- is checked for
    near-zero entries, the QR image of rank deficiency.  ``health=None``
    (default) attaches nothing: the zero-overhead NULL_HOOK path, pinned
    by redist-count equality and the unchanged qr/qr_tsqr comm goldens.

    ``abft`` opts into Huang-Abraham checksum guarding with per-panel
    transactional recovery (ISSUE 15; same contract as
    ``lu``/``cholesky``): pass ``True`` (report retrievable via
    ``resilience.last_abft_report('qr')``) or a caller-owned
    ``AbftGuard``.  The guarded schedule keeps ``panel=`` ('classic' and
    'tsqr' are both guarded) but ignores ``redist_path`` -- per-panel
    transactions pin the default hop-chain gathers.  ``abft=None``
    (default) never imports the resilience module: the unguarded sweep
    is bit-identical and its comm goldens unchanged."""
    _check_mcmr(A)
    m, n = A.gshape
    g = A.grid
    if isinstance(nb, str) or panel == "auto" or comm_precision == "auto" \
            or redist_path == "auto" or panel_impl == "auto":
        from ..tune.policy import resolve_knobs
        kn = resolve_knobs("qr", gshape=A.gshape, dtype=A.dtype, grid=g,
                           knobs={"nb": nb, "panel": panel,
                                  "panel_impl": panel_impl,
                                  "comm_precision": comm_precision,
                                  "redist_path": redist_path})
        nb, panel, comm_precision = kn["nb"], kn["panel"], \
            kn["comm_precision"]
        redist_path = kn.get("redist_path")
        panel_impl = kn.get("panel_impl")
    from ..redist.quantize import check_comm_precision
    check_comm_precision(comm_precision)
    if panel is None:
        panel = "classic"
    if panel not in ("classic", "tsqr"):
        raise ValueError(f"qr: unknown panel strategy {panel!r}; "
                         "expected 'classic', 'tsqr', or 'auto'")
    from ..kernels import resolve_panel
    plan = resolve_panel(panel_impl, dtype=A.dtype)
    if abft:
        from ..resilience.abft import abft_qr
        return abft_qr(A, nb=nb, precision=precision, panel=panel,
                       comm_precision=comm_precision, timer=timer,
                       health=health, abft=abft, plan=plan)
    tm = _phase_hook("qr", timer)
    hm = None
    if health:
        from ..resilience.health import attach_health
        tm, hm = attach_health("qr", health, tm, scale_from=A)
    tm.start()
    r, c = g.height, g.width
    ib = _blocksize(nb, math.lcm(r, c), min(m, n))
    kend = min(m, n)
    taus = []
    for k, s in enumerate(range(0, kend, ib)):
        e = min(s + ib, kend)
        nbw = e - s
        e_up = min(-(-e // c) * c, n)
        with tm.phase("panel", k) as ph:
            panel_ss = redistribute(view(A, rows=(s, m), cols=(s, e_up)),
                                    STAR, STAR,
                                    comm_precision=comm_precision,
                                    path=redist_path)
            Tk = None
            if panel == "tsqr":
                Pf, tau = _panel_qr_tsqr(panel_ss.local[:, :nbw], r,
                                         precision)
            else:
                Pf, tau, Tk = _panel_qr_dispatch(panel_ss.local[:, :nbw],
                                                 plan)
            Pf, = apply_fault("compute", (Pf,))
            taus.append(tau)
            ph.done(Pf, tau)
        with tm.phase("panel", k):
            if e_up > e:
                Pf_w = jnp.pad(Pf, ((0, 0), (0, e_up - e)))
            else:
                Pf_w = Pf
            Pf_ss = DistMatrix(Pf_w, (m - s, e_up - s), STAR, STAR, 0, 0, g)
            A = _update_cols_lt(A, redistribute(Pf_ss, MC, MR), (s, m),
                                (s, e_up), e)
        if e < n:
            with tm.phase("update", k) as ph:
                V = _panel_v(Pf)
                T = Tk if Tk is not None else _larft(V, tau)
                A = _update_cols_ge(
                    A, _wy_apply(A, V, jnp.conj(T).T, (s, m), (s, n),
                                 precision), (s, m), (s, n), e)
                ph.done(A)
    _record_qr_nb(A, ib)
    if hm is not None:
        hm.report()
    return A, jnp.concatenate(taus) if taus else jnp.zeros((0,), A.dtype)


def _record_qr_nb(Ap: DistMatrix, ib: int) -> None:
    """Attach the block size a factorization actually used to the packed
    matrix (frozen dataclass => object.__setattr__).  Host-side metadata
    only: lost across jit/pytree boundaries, where callers must keep
    passing a consistent ``nb`` themselves."""
    object.__setattr__(Ap, "_qr_nb", int(ib))


def _applyq_blocksize(Ap: DistMatrix, nb, grain: int, kend: int) -> int:
    """The blocking :func:`apply_q` must sweep with: default to the block
    size recorded by :func:`qr`, and REFUSE a mismatching explicit ``nb``
    (different panel boundaries silently produce a wrong Q)."""
    rec = getattr(Ap, "_qr_nb", None)
    if nb is None:
        return rec if rec is not None else _blocksize(None, grain, kend)
    if isinstance(nb, str):
        from ..tune.policy import resolve_knobs
        nb = resolve_knobs("qr", gshape=Ap.gshape, dtype=Ap.dtype,
                           grid=Ap.grid, knobs={"nb": nb})["nb"]
    ib = _blocksize(nb, grain, kend)
    if rec is not None and ib != rec:
        raise ValueError(
            f"apply_q: nb={nb!r} derives block size {ib}, but this packed "
            f"factor was produced by qr() with block size {rec}; pass "
            "nb=None to reuse the factorization's blocking")
    return ib


def apply_q(Ap: DistMatrix, tau, B: DistMatrix, orient: str = "N",
            nb: int | str | None = None, precision=None) -> DistMatrix:
    """B := Q B ('N') or Q^H B ('C'), Q from (packed, tau)
    (``qr::ApplyQ`` / ``ApplyPackedReflectors``).

    ``nb`` MUST match the factorization's blocking.  The default
    (``None``) reuses the block size :func:`qr` recorded on ``Ap``; an
    explicit ``nb`` that derives different panel boundaries raises
    ``ValueError`` instead of silently applying a wrong Q."""
    _check_mcmr(Ap, B)
    m, n = Ap.gshape
    if B.gshape[0] != m:
        raise ValueError(f"B height {B.gshape[0]} != {m}")
    g = Ap.grid
    r, c = g.height, g.width
    kend = min(m, n)
    ib = _applyq_blocksize(Ap, nb, math.lcm(r, c), kend)
    starts = list(range(0, kend, ib))
    if orient == "N":
        starts = starts[::-1]
    for s in starts:
        e = min(s + ib, kend)
        nbw = e - s
        e_up = min(-(-e // c) * c, n)
        panel = redistribute(view(Ap, rows=(s, m), cols=(s, e_up)), STAR, STAR)
        V = _panel_v(panel.local[:, :nbw])
        T = _larft(V, tau[s:e])
        Tm = jnp.conj(T).T if orient == "C" else T
        B = update_view(B, _wy_apply(B, V, Tm, (s, m), None, precision),
                        rows=(s, m))
    return B


def explicit_q(Ap: DistMatrix, tau, nb: int | None = None,
               precision=None) -> DistMatrix:
    """The m x m unitary Q as a DistMatrix (``qr::ExplicitUnitary``)."""
    from ..matrices.basic import identity
    I = identity(Ap.gshape[0], grid=Ap.grid, dtype=Ap.dtype)
    return apply_q(Ap, tau, I, orient="N", nb=nb, precision=_hi(precision))


def _stack_qr_thin_q(X: DistMatrix, sc, nb: int | None = None,
                     precision=None):
    """The thin Q of QDWH's stack ``[sc X; I] = Q R`` ((m + n) x n, X m x n
    with m >= n) as its two blocks ``(Q1, Q2)``, m x n and n x n: what
    :func:`qr` of the stack and :func:`apply_q` on ``[I; 0]`` give, over
    the rows and columns that are not structurally zero.

    The lower block starts as the identity and stays upper triangular, so
    the panel at columns ``[s, e)`` has non-zero rows ``(s, m + e)`` only:
    it is gathered, reduced and applied over those (m + e - s rows,
    whatever s) and the rows below are never read or written.  Each
    panel's T is kept, and the backward sweep that forms the thin Q
    builds ``[I; 0]``'s columns ``[s, e)`` under panel s directly
    (``[I; 0] - V T V_top^H``: ``V^H`` of an identity's columns is a
    slice) and applies the panel to columns ``(e, n)``, which alone are
    non-zero in its rows.  The same reflectors, the same products less
    those with exact zeros: Q2 comes out upper triangular exactly.  R is
    not returned (its diagonal blocks lie in the packed panels).

    On a grid a row range ends on the column distribution's grain: a
    panel whose ``m + e`` does not keeps rows ``(s, m + n)``.  Ticks
    ``qdwh_stack_qr{route}``: ``structured``, or ``dense`` where some
    panel kept all its rows.  The factorization's ops carry ``el.qr``
    with ``k<panel>/panel`` and ``/update`` as :func:`qr`'s do, the thin
    Q's ``el.thin_q/k<panel>/apply``.  Only a caller that KNOWS its lower
    block is the identity may come here (``funcs._qdwh_step_qr``): a
    traced operand does not show it."""
    from ..kernels import resolve_panel
    _check_mcmr(X)
    m, n = X.gshape
    if m < n:
        raise ValueError(
            f"the stack's upper block must be tall, got {X.gshape}")
    g = X.grid
    r = g.height
    rows = m + n
    ib = _blocksize(nb, math.lcm(r, g.width), n)
    plan = resolve_panel(None, dtype=X.dtype)
    # (s, e, hi): the panel at columns [s, e) lives in rows (s, hi)
    starts = range(0, n, ib)
    ends = [min(s + ib, n) for s in starts]
    spans = [(s, e, m + e if (m + e) % r == 0 or e == n else rows)
             for s, e in zip(starts, ends)]
    _metrics.inc("qdwh_stack_qr", route="structured" if all(
        hi == m + e for _s, e, hi in spans) else "dense")

    def place(M, block, s, e, hi):
        """``M`` with the replicated ``block`` at rows (s, hi), columns
        [s, e)."""
        block_ss = DistMatrix(block, block.shape, STAR, STAR, 0, 0, g)
        return update_view(M, redistribute(block_ss, MC, MR),
                           rows=(s, hi), cols=(s, e))

    S = vstack(X.with_local(sc * X.local), shift_diagonal(_blank(n, n, X), 1))
    Ts = []
    with jax.named_scope("el.qr"):
        tm = _phase_hook("qr", None)
        tm.start()
        for k, (s, e, hi) in enumerate(spans):
            with tm.phase("panel", k):
                P = redistribute(view(S, rows=(s, hi), cols=(s, e)),
                                 STAR, STAR)
                Pf, tau, Tk = _panel_qr_dispatch(P.local, plan)
                S = place(S, Pf, s, e, hi)
            with tm.phase("update", k):
                V = _panel_v(Pf)
                Ts.append(Tk if Tk is not None else _larft(V, tau))
                if e < n:
                    S = update_view(
                        S, _wy_apply(S, V, jnp.conj(Ts[k]).T, (s, hi), (e, n),
                                     precision), rows=(s, hi), cols=(e, n))
    Q = _blank(rows, n, X)
    with jax.named_scope("el.thin_q"):
        tm = _phase_hook("thin_q", None)
        tm.start()
        for k, (s, e, hi) in reversed(list(enumerate(spans))):
            with tm.phase("apply", k):
                P = redistribute(view(S, rows=(s, hi), cols=(s, e)),
                                 STAR, STAR)
                V = _panel_v(P.local)
                # columns [s, e) of [I; 0] under this panel
                W = jnp.matmul(Ts[k], jnp.conj(V[:e - s]).T,
                               precision=_hi(precision))
                Q = place(Q, jnp.eye(hi - s, e - s, dtype=V.dtype)
                          - jnp.matmul(V, W, precision=_hi(precision)),
                          s, e, hi)
                if e < n:
                    Q = update_view(
                        Q, _wy_apply(Q, V, Ts[k], (s, hi), (e, n), precision),
                        rows=(s, hi), cols=(e, n))
    return (interior_view(Q, (0, m), (0, n)),
            interior_view(Q, (m, rows), (0, n)))


@_scoped("el.least_squares")
def least_squares(A: DistMatrix, B: DistMatrix, nb: int | None = None,
                  precision=None, abft=None) -> DistMatrix:
    """Minimize ||A X - B||_F for m >= n via QR (``El::LeastSquares``,
    dense path of ``src/lapack_like/euclidean_min/LeastSquares.cpp``).

    Two routes, chosen from the shapes and the grid
    (:func:`_takes_tall_route`; the counter ``lstsq_route{kind}`` says
    which):

    * ``tall`` (``qr::TS``): on a grid of p > 1 chips, where every
      chip's share of the rows, m / p, is at least 8192 n and n <= 256
      (the aspect and width the route was measured at), B is no wider
      than A, and the entries are real, the alignments zero and no
      ``abft`` is asked for: A and B go to
      [VC,STAR] once, each chip factors its own rows by Householder QR,
      the p small R factors are gathered once and their stack factored
      the same on every chip, and R X = Y is solved there.  No chip ever
      holds more of A than its m / p rows.  ``nb`` is not used.
    * ``blocked``, everything else (square and moderately tall
      problems): the blocked Householder QR below, Q^H B via packed
      reflectors, then a distributed triangular solve against the
      interior-extracted R.

    ``abft`` threads through to :func:`qr` (ISSUE 15): the factorization
    -- the solve's whole O(m n^2) fault surface -- runs checksum-guarded
    with panel-granular recovery, so the serve executor's ``grid_qr``
    escalation rung is corruption-attested end to end."""
    from ..redist.interior import interior_view      # qr <- interior is cycle-free
    from ..blas.level1 import make_trapezoidal
    _check_mcmr(A, B)
    m, n = A.gshape
    if m < n:
        raise ValueError("least_squares requires m >= n (tall)")
    if B.gshape[0] != m:
        raise ValueError(f"B height {B.gshape[0]} != {m}")
    tall = _takes_tall_route(A, B, abft)
    _metrics.inc("lstsq_route", kind="tall" if tall else "blocked")
    if tall:
        return _least_squares_tall(A, B, precision)
    Ap, tau = qr(A, nb=nb, precision=_hi(precision), abft=abft)
    Y = apply_q(Ap, tau, B, orient="C", nb=nb, precision=_hi(precision))
    R = make_trapezoidal(interior_view(Ap, (0, n), (0, n)), "U")
    Y1 = interior_view(Y, (0, n), (0, B.gshape[1]))
    return trsm("L", "U", "N", R, Y1, nb=nb, precision=_hi(precision))


# ---------------------------------------------------------------------
# Column-pivoted QR (Businger-Golub / geqp3)
# ---------------------------------------------------------------------

@partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 7))
def _panel_qp(stor, colnorms, s: int, m: int, n: int, nbw: int,
              Sc: int, Sr: int):
    """One left-looking pivoted panel (LAPACK ``laqps`` analog).

    Columns are identified by GLOBAL id throughout (the F accumulator is
    indexed by global column), so no physical swaps happen inside the
    panel; ``stor`` is the panel-start full storage snapshot.  Per column:
    one traced-index column fetch + one row fetch + corrections, one
    reflector, and the norm downdates.  Returns (V, F, packed R+v panel,
    tau, jpvt, updated colnorms)."""
    mt = m - s
    dtype = stor.dtype
    rdtype = jnp.zeros((), dtype).real.dtype
    ridx = jnp.arange(mt)
    lr = -(-m // Sc)
    lc = -(-n // Sr)

    def snap_col(gcol):
        scol = (gcol % Sr) * lc + gcol // Sr
        colf = lax.dynamic_index_in_dim(stor, scol, axis=1, keepdims=False)
        grow = s + jnp.arange(mt)
        srow = (grow % Sc) * lr + grow // Sc
        return jnp.take(colf, srow, axis=0)

    def snap_row(grow):
        srow = (grow % Sc) * lr + grow // Sc
        rowf = lax.dynamic_index_in_dim(stor, srow, axis=0, keepdims=False)
        gcol = jnp.arange(n)
        scol = (gcol % Sr) * lc + gcol // Sr
        return jnp.take(rowf, scol, axis=0)

    def body(k, carry):
        V, F, P, tau, jpvt, norms = carry
        gc = jnp.argmax(norms)
        jpvt = jpvt.at[k].set(gc.astype(jnp.int32))
        c = snap_col(gc) - V @ jnp.conj(F[gc, :])
        v, tq, beta = _panel_qp_larfg(c, k, ridx, dtype)
        # packed column: R entries above the pivot, beta on it, v tail below
        pc = jnp.where(ridx < k, c, 0).at[k].set(jnp.asarray(beta, dtype))
        pc = jnp.where(ridx > k, v, pc)
        P = P.at[:, k].set(pc)
        V = V.at[:, k].set(v)
        tau = tau.at[k].set(tq)
        # F[:, k] = tq * (A0^H v - F V^H v): base is precomputed outside?
        # A0^H v needs the distributed trailing view -- computed by caller
        # via a matmul on the snapshot strip (mt x n): here stor strip
        # already replicated? No: use the full-width strip gathered by the
        # caller.  (See _strip below -- closed over.)
        base = jnp.conj(_strip).T @ v
        f = tq * (base - F @ (jnp.conj(V).T @ v))
        F = F.at[:, k].set(f.astype(dtype))
        # R row k across all columns (V/F now include column k, whose
        # V[k, k] = 1 carries the new reflector's contribution)
        rowk = snap_row(s + k) - V[k, :] @ jnp.conj(F).T
        down = jnp.abs(rowk) ** 2
        # downdate only live columns; used ones carry the -1 sentinel
        norms = jnp.where(norms < 0, norms,
                          jnp.sqrt(jnp.maximum(norms ** 2 - down, 0.0)))
        norms = norms.at[gc].set(-1.0)
        return V, F, P, tau, jpvt, norms

    # full-width row strip of the snapshot (rows [s, m) in global order):
    grow = s + jnp.arange(mt)
    srow = (grow % Sc) * lr + grow // Sc
    gcol = jnp.arange(n)
    scol = (gcol % Sr) * lc + gcol // Sr
    _strip = jnp.take(jnp.take(stor, srow, axis=0), scol, axis=1)

    init = (jnp.zeros((mt, nbw), dtype), jnp.zeros((n, nbw), dtype),
            jnp.zeros((mt, nbw), dtype), jnp.zeros((nbw,), dtype),
            jnp.zeros((nbw,), jnp.int32), colnorms.astype(rdtype))
    return lax.fori_loop(0, nbw, body, init)


def _panel_qp_larfg(col, piv, ridx, dtype):
    from .condense import _larfg_at
    return _larfg_at(col, piv, ridx, dtype)


def qr_col_piv(A: DistMatrix, nb: int | None = None, precision=None):
    """Column-pivoted QR ``A[:, jpvt] = Q R`` (``El::qr::BusingerGolub`` /
    LAPACK geqp3).  Returns ``(packed, tau, jpvt)`` in geqrf packing with
    greedy max-norm pivot order (R's diagonal is non-increasing in
    magnitude).

    Norm downdates use the squared-recurrence with clamping but WITHOUT
    LAPACK's cancellation-triggered exact recomputation (documented
    deviation; pathological cancellation can perturb late pivot choices).
    """
    _check_mcmr(A)
    m, n = A.gshape
    g = A.grid
    r, c = g.height, g.width
    Sc, Sr = A.col_stride, A.row_stride
    ib = _blocksize(nb, math.lcm(r, c), min(m, n))
    kend = min(m, n)
    # initial exact column norms (storage cols are global cols)
    from ..blas.level1 import _global_indices
    ns = jnp.sqrt(jnp.sum(jnp.abs(A.local) ** 2, axis=0))
    _, J = _global_indices(A)
    colnorms = jnp.zeros((n,), ns.dtype).at[J].set(ns, mode="drop")
    Awork = A
    panels, taus, jps = [], [], []
    for s in range(0, kend, ib):
        e = min(s + ib, kend)
        nbw = e - s
        V, F, P, tau, jpvt, colnorms = _panel_qp(
            Awork.local, colnorms, s, m, n, nbw, Sc, Sr)
        panels.append(P)
        taus.append(tau)
        jps.append(jpvt)
        if e < kend or e < n:
            # trailing update of rows [s, m) across the full width
            strip = view(Awork, rows=(s, m))
            Vmc = redistribute(DistMatrix(V, (m - s, nbw), STAR, STAR, 0, 0,
                                          g), MC, STAR)
            FH = redistribute(DistMatrix(jnp.conj(F).T, (nbw, n), STAR, STAR,
                                         0, 0, g), STAR, MR)
            upd = jnp.matmul(Vmc.local, FH.local, precision=_hi(precision))
            Awork = update_view(Awork, strip.with_local(
                strip.local - upd.astype(A.dtype)), rows=(s, m))
    jpvt = jnp.concatenate(jps)
    tau = jnp.concatenate(taus)
    # assemble: permute columns into pivot order, then overwrite each
    # panel's rows with its packed block
    from .lu import permute_cols, _update_cols_lt
    full_perm = jnp.concatenate(
        [jpvt, _complement(jpvt, n)]) if n > kend else jpvt
    Ap = permute_cols(Awork, full_perm)
    for i, s in enumerate(range(0, kend, ib)):
        e = min(s + ib, kend)
        nbw = e - s
        e_up = min(-(-e // c) * c, n)
        P = panels[i]
        if e_up > e:
            P = jnp.pad(P, ((0, 0), (0, e_up - e)))
        blk = DistMatrix(P, (m - s, e_up - s), STAR, STAR, 0, 0, g)
        Ap = _update_cols_lt(Ap, redistribute(blk, MC, MR), (s, m),
                             (s, e_up), e)
    _record_qr_nb(Ap, ib)
    return Ap, tau, jpvt


def _complement(jpvt, n: int):
    """Global columns not chosen as pivots, ascending (traced)."""
    mask = jnp.ones((n,), bool).at[jpvt].set(False)
    return jnp.nonzero(mask, size=n - jpvt.shape[0])[0]


# ---------------------------------------------------------------------
# LQ (via the QR of the adjoint)
# ---------------------------------------------------------------------

def lq(A: DistMatrix, nb: int | None = None, precision=None,
       redist_path: str | None = None):
    """LQ factorization ``A = L Q`` with L lower-trapezoidal and Q having
    orthonormal rows (``El::LQ``): computed as the QR of ``A^H``
    (``A^H = Q_r R  =>  A = R^H Q_r^H``).  Returns ``(packed, tau)`` where
    ``packed`` is the geqrf-packed QR of ``A^H`` ((n, m)-shaped); use
    :func:`apply_q_lq` / :func:`explicit_l` to consume it.
    ``redist_path='direct'`` collapses the entry transpose-exchange from a
    3-hop chain to one one-shot exchange and rides the QR panel gathers."""
    from ..redist.engine import transpose_dist
    Ah = redistribute(transpose_dist(A, conj=True), MC, MR, path=redist_path)
    return qr(Ah, nb=nb, precision=_hi(precision), redist_path=redist_path)


def apply_q_lq(Ap: DistMatrix, tau, B: DistMatrix, orient: str = "N",
               nb: int | None = None, precision=None) -> DistMatrix:
    """B := Q B ('N') or Q^H B ('C') with Q the LQ unitary (Q = Q_r^H of
    the underlying adjoint-QR)."""
    flip = "C" if orient == "N" else "N"
    return apply_q(Ap, tau, B, orient=flip, nb=nb, precision=_hi(precision))


def explicit_l(Ap: DistMatrix) -> DistMatrix:
    """The explicit (m, min(m,n)) lower-trapezoidal L from :func:`lq`'s
    packing (L = R^H of the adjoint QR; shape is read from ``Ap``)."""
    from ..redist.engine import transpose_dist
    from ..redist.interior import interior_view
    from ..blas.level1 import make_trapezoidal
    n_, m_ = Ap.gshape                      # Ap is the packed QR of A^H
    k = min(n_, m_)
    R = make_trapezoidal(interior_view(Ap, (0, k), (0, m_)), "U")
    return redistribute(transpose_dist(R, conj=True), MC, MR)


def rq(A: DistMatrix, nb: int | None = None, precision=None):
    """RQ factorization ``A = R Q`` (``El::RQ``) with R (m, k) upper
    triangular/trapezoidal against the BOTTOM-RIGHT corner and Q (k, n)
    having orthonormal rows (k = min(m, n)).

    Computed via the exchange identity: with J the anti-identity,
    J_m A J_n = L W (LQ)  =>  A = (J_m L J_k) (J_k W J_n), and the flip of
    a lower-trapezoidal L is upper-trapezoidal.  Returns explicit (R, Q)
    (the reference's packed-reflector form is reachable through
    :func:`lq` on the flipped matrix)."""
    from .lu import permute_rows, permute_cols
    m, n = A.gshape
    k = min(m, n)
    rev_m = jnp.arange(m)[::-1]
    rev_n = jnp.arange(n)[::-1]
    rev_k = jnp.arange(k)[::-1]
    Af = permute_cols(permute_rows(A, rev_m), rev_n)     # J_m A J_n
    packed, tau = lq(Af, nb=nb, precision=_hi(precision))
    L = explicit_l(packed)                               # (m, k)
    # W = first k rows of the (n, n) LQ unitary.  Rows cannot be sliced
    # before a left-apply, but W^H = Q^H [I_k; 0]: apply Q^H to the
    # (n, k) identity SLAB and adjoint -- O(n k) instead of O(n^2).
    from ..matrices.basic import identity
    from ..redist.interior import interior_view
    from ..redist.engine import transpose_dist
    Ik = interior_view(identity(n, grid=A.grid, dtype=A.dtype), (0, n),
                       (0, k)) if k < n \
        else identity(n, grid=A.grid, dtype=A.dtype)
    Wh = apply_q_lq(packed, tau, Ik, orient="C", nb=nb,
                    precision=_hi(precision))            # (n, k) = W^H
    W = redistribute(transpose_dist(Wh, conj=True), MC, MR)
    R = permute_cols(permute_rows(L, rev_m), rev_k)
    Q = permute_cols(permute_rows(W, rev_k), rev_n)
    return R, Q


# ---------------------------------------------------------------------
# TSQR (tall-skinny): every chip factors the rows it holds
# ---------------------------------------------------------------------
#
# The route of ``least_squares`` and ``tsqr`` for m >> n (``qr::TS``).
# A chip's rows are all of [VC,STAR]'s local block, (lr, n).  It is
# factored by Householder QR where it lies, in TRANSPOSED storage: a
# column of A is a row of sublanes, and an unblocked panel of
# ``_TALL_PANEL`` columns is one row of whole float32 tiles, so nothing
# the column loop touches is padded (a panel stored (lr, 8) would pad
# its 8 lanes to 128).  The first n rows (the ``head``, n x n, where R
# and the reflectors' unit triangle live) are kept apart from the rest
# (the ``body``, n x (lr - n) transposed), so that no op on the body needs
# a mask.  Panels are joined by recursion on the column range (Elmroth &
# Gustavson): factor the left half, apply its block reflector
# I - V T V^T to the right half, factor that, and merge the two T.  All
# products against the body carry the caller's ``precision``.

#: columns of an unblocked panel: the sublanes of one float32 tile
_TALL_PANEL = 8
#: the widest operand that takes the tall route: the benchmark's cell's.
#: The route unrolls its panels (n / 8 column loops and as many block
#: reflectors, 82,036 optimized HLO lines at 256 columns) and was
#: compiled and timed at no other width (PERF.md 6 and 7, PR 43)
_TALL_MAX_COLS = 256
#: rows a chip must hold for each column of A: the cell's 2^21 rows a chip
#: for 256 columns, the one aspect the route was timed at on a grid
_TALL_ASPECT = 8192
#: rows of the body one partial sum runs over (below)
_TALL_CHUNK = 8192

# Every inner product of the factorization runs over a chip's rows, 2^21 of
# them in the benchmark's cell, and a float32 accumulator that long loses
# five digits; on columns that are nearly parallel the loss is what decides
# the answer (the rounding of the PRODUCTS averages out over so many terms,
# whatever their precision: PERF.md 6, PR 43).  So the body is kept in
# chunks of ``_TALL_CHUNK`` rows, (C, n, L): a product over the rows is C
# partial products, each accumulated over one chunk, summed by halves.


def _pair_sum(x):
    """Sum over the leading axis by halves: log2(C) roundings a term, where
    a running sum makes C."""
    while x.shape[0] > 1:
        if x.shape[0] % 2:
            x = jnp.concatenate([x, jnp.zeros_like(x[:1])])
        x = x[0::2] + x[1::2]
    return x[0]


def _tall_chunks(rows):
    """(mb, k) rows of a chip's block -> (C, k, L) chunks, transposed;
    zero rows fill the last chunk (they change no product)."""
    mb, k = rows.shape
    L = max(min(_TALL_CHUNK, mb), 1)
    C = max(-(-mb // L), 1)
    rows = jnp.pad(rows, ((0, C * L - mb), (0, 0)))
    return rows.reshape(C, L, k).transpose(0, 2, 1)


def _tall_rows(chunks, mb):
    """The inverse of :func:`_tall_chunks`: (C, k, L) -> (mb, k)."""
    C, k, L = chunks.shape
    return chunks.transpose(0, 2, 1).reshape(C * L, k)[:mb]


def _rows_dot(X, Y, precision):
    """``X Y^T`` over the rows of two chunked bodies (C, k, L), (C, j, L):
    (k, j), a partial product a chunk, summed by halves."""
    return _pair_sum(jnp.einsum("ckl,cjl->ckj", X, Y, precision=precision))


def _rows_mix(M, Y, precision):
    """``M Y`` for (k, j) ``M`` and a chunked body ``Y`` (C, j, L)."""
    return jnp.einsum("kj,cjl->ckl", M, Y, precision=precision)


def _tall_panel(Ph, Pb, c0):
    """Unblocked Householder QR of the ``w <= _TALL_PANEL`` columns
    ``c0 .. c0 + w`` of a chip's block, transposed: ``Ph`` (w, n) their
    head rows, ``Pb`` (C, w, L) their body.  LAPACK larfg conventions, as
    :func:`_panel_qr`.  Returns ``(packed head, Vh, Vb, T)``: R's columns
    on and left of the diagonal with the reflectors' tails right of it,
    the reflectors' heads alone (unit at the diagonal), their bodies, and
    the larft triangle of the w reflectors (``Q = I - V T V^T``).

    A column costs the body two passes: the dots of every row with row j
    (its norm, the other columns' products with it and, from the rows
    already reduced, the new column of V^T V for T), then one fused
    update."""
    w, n = Ph.shape
    dt = Ph.dtype
    lane = jnp.arange(n)
    row = jnp.arange(w)

    def column(j, state):
        Ph, Vh, Pb, T = state
        c = c0 + j
        hrow = Ph[j]
        alpha = hrow[c]
        htail = jnp.where(lane > c, hrow, 0)
        brow = lax.dynamic_index_in_dim(Pb, j, axis=1)          # (C, 1, L)
        dots = _pair_sum(jnp.sum(Pb * brow, axis=2))            # (w,)
        sigma = jnp.sum(htail * htail) + dots[j]
        anorm = jnp.sqrt(alpha * alpha + sigma)
        beta = jnp.where(alpha < 0, anorm, -anorm)
        degenerate = anorm == 0
        safe_beta = jnp.where(degenerate, 1.0, beta).astype(dt)
        tau = jnp.where(degenerate, 0.0,
                        (safe_beta - alpha) / safe_beta).astype(dt)
        denom = alpha - safe_beta
        scale = (1.0 / jnp.where(denom == 0, 1.0, denom)).astype(dt)
        vh = jnp.where(lane == c, jnp.where(degenerate, 0.0, 1.0),
                       htail * scale).astype(dt)
        # v_j against every row: rows k > j still hold columns of A
        # (w_k = v_j^T a_k), rows k < j hold reflectors (V^T V for T)
        d = jnp.where(row < j, Vh @ vh, Ph @ vh) + dots * scale
        wk = jnp.where(row > j, tau * d, 0)
        packed = jnp.where(lane > c, vh, jnp.where(lane == c, beta, hrow))
        Ph = jnp.where((row == j)[:, None], packed[None, :],
                       Ph - wk[:, None] * vh[None, :])
        # body: rows k > j lose wk * v_j, row j becomes v_j, rows k < j stay
        Pb = jnp.where((row == j)[None, :, None], brow * scale,
                       Pb - (wk * scale)[None, :, None] * brow)
        Vh = jnp.where((row == j)[:, None], vh[None, :], Vh)
        tcol = jnp.where(row < j, -tau * (T @ jnp.where(row < j, d, 0)),
                         jnp.where(row == j, tau, 0))
        T = jnp.where((row == j)[None, :], tcol[:, None], T)
        return Ph, Vh, Pb, T

    return lax.fori_loop(
        0, w, column, (Ph, jnp.zeros_like(Ph), Pb, jnp.zeros((w, w), dt)))


def _tall_factor(H, Ab, r0, h, precision):
    """Householder QR of columns ``r0 .. r0 + h`` of a chip's block in
    place: ``H`` (h, n) are their head rows, ``Ab`` (C, n, L) the WHOLE
    body, of which rows ``r0 .. r0 + h`` are read and rewritten (columns
    left of ``r0`` already hold reflectors, right of ``r0 + h`` are not
    touched).  Returns ``(packed head, Vh, Ab, T)`` as
    :func:`_tall_panel`, T the larft triangle of all h reflectors."""
    if h <= _TALL_PANEL:
        Ph, Vh, Pb, T = _tall_panel(H, Ab[:, r0:r0 + h], r0)
        return Ph, Vh, Ab.at[:, r0:r0 + h].set(Pb), T
    h1 = -(-(h // 2) // _TALL_PANEL) * _TALL_PANEL
    mm = partial(jnp.matmul, precision=precision)
    P1, V1, Ab, T1 = _tall_factor(H[:h1], Ab, r0, h1, precision)
    lo, mid, hi = r0, r0 + h1, r0 + h
    # right half <- (I - V1 T1 V1^T)^T right half, rows being columns:
    # X -= ((X V1) T1) V1^T
    M = mm(mm(H[h1:], V1.T)
           + _rows_dot(Ab[:, mid:hi], Ab[:, lo:mid], precision), T1)
    H2 = H[h1:] - mm(M, V1)
    Ab = Ab.at[:, mid:hi].set(
        Ab[:, mid:hi] - _rows_mix(M, Ab[:, lo:mid], precision))
    P2, V2, Ab, T2 = _tall_factor(H2, Ab, mid, h - h1, precision)
    # larft by halves: T12 = -T1 (V1^T V2) T2
    S = mm(V1, V2.T) + _rows_dot(Ab[:, lo:mid], Ab[:, mid:hi], precision)
    T12 = -mm(mm(T1, S), T2)
    T = jnp.concatenate([
        jnp.concatenate([T1, T12], axis=1),
        jnp.concatenate([jnp.zeros((h - h1, h1), T1.dtype), T2], axis=1)])
    return (jnp.concatenate([P1, P2]), jnp.concatenate([V1, V2]), Ab, T)


def _tall_qr(a, precision):
    """Householder QR of a chip's (lr, n) block, lr >= n, where it lies.
    Returns ``(R, Vh, Vb, T)``: R (n, n) upper triangular and the block
    reflector ``Q = I - V T V^T`` with ``V^T = [Vh | Vb]`` ((n, n) unit
    upper triangular | the body's (C, n, L) chunks)."""
    n = a.shape[1]
    Pk, Vh, Vb, T = _tall_factor(a[:n].T, _tall_chunks(a[n:]), 0, n,
                                 precision)
    return jnp.tril(Pk).T, Vh, Vb, T


def _tall_apply_qt(Vh, Vb, T, b, precision):
    """The first n rows of ``Q^T b`` for a chip's (lr, k) block ``b`` and
    the block reflector of :func:`_tall_qr`; the rows below them are not
    formed.  Returns (n, k)."""
    n = Vh.shape[0]
    mm = partial(jnp.matmul, precision=precision)
    bh = b[:n].T                                       # (k, n)
    M = mm(mm(bh, Vh.T) + _rows_dot(_tall_chunks(b[n:]), Vb, precision), T)
    return (bh - mm(M, Vh)).T


def _tall_explicit_q(Vh, Vb, T, C, rows, precision):
    """``Q [C; 0]`` for an (n, k) block ``C``: (rows, k), head rows
    first."""
    mm = partial(jnp.matmul, precision=precision)
    M = mm(mm(C.T, Vh.T), T.T)                         # (k, n)
    body = _tall_rows(-_rows_mix(M, Vb, precision), rows - Vh.shape[0])
    return jnp.concatenate([(C.T - mm(M, Vh)).T, body])


def _tsqr_tree_stage(R1, grid, precision):
    """One all-gather of the p chips' R factors in VC rank order and the
    QR of their stack, the same on every chip: ``(R, Vh, Vb, T)`` of the
    (p n, n) stack as :func:`_tall_qr`."""
    n = R1.shape[0]
    p = grid.size
    _metrics.inc("tsqr_tree_bytes", p * n * n * R1.dtype.itemsize)
    Rs = lax.all_gather(R1, ("mr", "mc"), axis=0)       # VC rank order
    return _tall_qr(Rs.reshape(p * n, n), precision)


@_scoped("el.tsqr")
def tsqr(A: DistMatrix, precision=None):
    """Tall-skinny QR of a [VC,STAR] matrix (``qr::TS``): each chip's
    Householder QR of its own rows, one all-gather of the p small R
    factors and the QR of their stack, the same on every chip.
    Returns (Q [VC,STAR] with orthonormal columns, R [STAR,STAR]).
    Real dtypes only (a complex A is refused: the reflectors here take no
    conjugates); every product runs at ``precision`` (None: HIGHEST)."""
    if A.dist != (VC, STAR) or (A.calign, A.ralign) != (0, 0):
        raise ValueError(f"tsqr expects zero-aligned [VC,STAR], got {A}")
    if not jnp.issubdtype(A.dtype, jnp.floating):
        raise ValueError(
            f"tsqr factors real floating-point matrices, got {A.dtype}")
    m, k = A.gshape
    g = A.grid
    if m < k:
        raise ValueError("tsqr needs m >= k")
    if A.local_rows < k:
        raise ValueError(
            f"tsqr: each of the {g.size} chips must hold at least as many "
            f"rows as A has columns; {m} rows give a chip {A.local_rows}, "
            f"A has {k} columns")
    prec = _hi(precision)

    def f(a):
        with jax.named_scope("k00/local"):
            _metrics.inc("tsqr_leaf")
            R, Vh, Vb, T = _tall_qr(a, prec)
        C = jnp.eye(k, dtype=a.dtype)       # this chip's block of the stack's Q
        if g.size > 1:
            with jax.named_scope("k00/tree"):
                R, Vh2, Vb2, T2 = _tsqr_tree_stage(R, g, prec)
            with jax.named_scope("k00/applyq"):
                Q2 = _tall_explicit_q(Vh2, Vb2, T2, C, g.size * k, prec)
                C = lax.dynamic_slice_in_dim(
                    Q2, rank_of(VC, g.height, g.width) * k, k, axis=0)
        with jax.named_scope("k00/applyq"):
            return _tall_explicit_q(Vh, Vb, T, C, a.shape[0], prec), R

    Qs, Rs = shard_map(
        f, mesh=g.mesh, in_specs=(A.spec,),
        out_specs=(A.spec, PartitionSpec(None, None)), check_vma=False,
    )(A.local)
    Q = DistMatrix(Qs, (m, k), VC, STAR, 0, 0, g)
    R = DistMatrix(Rs, (k, k), STAR, STAR, 0, 0, g)
    return Q, R


def _takes_tall_route(A: DistMatrix, B: DistMatrix, abft) -> bool:
    """The rule of :func:`least_squares`, held to what was measured on the
    chip (PERF.md 6, PR 43: 8,388,608 x 256 on 2x2): a grid of several
    chips (on one chip nothing is gathered by the blocked route either),
    every chip's slab of A, m / p rows, at least ``_TALL_ASPECT`` times
    taller than A is wide, at most ``_TALL_MAX_COLS`` columns, B no wider
    than A, real entries, and no checksum guard asked for (the guard is
    the blocked route's)."""
    m, n = A.gshape
    return (not abft and A.grid.size > 1 and 0 < n <= _TALL_MAX_COLS
            and m >= _TALL_ASPECT * n * A.grid.size
            and B.gshape[1] <= n
            and jnp.issubdtype(A.dtype, jnp.floating)
            and A.dtype == B.dtype
            and (A.calign, A.ralign, B.calign, B.ralign) == (0, 0, 0, 0))


@_scoped("el.tsqr")
def _least_squares_tall(A: DistMatrix, B: DistMatrix, precision):
    """The tall-skinny route of :func:`least_squares`, on a grid of
    several chips: rows to the chips
    ([MC,MR] -> [VC,STAR], once for A and once for B), each chip's
    Householder QR of its own rows and its n rows of Q^T B, one
    all-gather of the p R factors (and one of the p blocks of Q^T B), the
    QR of the stack the same on every chip, and R X = Y solved there."""
    g = A.grid
    n, nrhs = A.gshape[1], B.gshape[1]
    prec = _hi(precision)
    Av = redistribute(A, VC, STAR)
    Bv = redistribute(B, VC, STAR)

    def f(a, b):
        with jax.named_scope("k00/local"):
            _metrics.inc("tsqr_leaf")
            R, Vh, Vb, T = _tall_qr(a, prec)
        with jax.named_scope("k00/applyq"):
            Y = _tall_apply_qt(Vh, Vb, T, b, prec)
        with jax.named_scope("k00/tree"):
            R, Vh, Vb, T = _tsqr_tree_stage(R, g, prec)
        with jax.named_scope("k00/applyq"):
            Ys = lax.all_gather(Y, ("mr", "mc"), axis=0)
            Y = _tall_apply_qt(Vh, Vb, T, Ys.reshape(g.size * n, nrhs), prec)
        with jax.named_scope("k00/solve"):
            return lax.linalg.triangular_solve(
                R, Y, left_side=True, lower=False)

    X = shard_map(f, mesh=g.mesh, in_specs=(Av.spec, Bv.spec),
                  out_specs=PartitionSpec(None, None), check_vma=False)(
                      Av.local, Bv.local)
    return redistribute(DistMatrix(X, (n, nrhs), STAR, STAR, 0, 0, g),
                        MC, MR)
