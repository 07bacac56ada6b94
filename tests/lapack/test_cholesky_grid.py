"""The grid branch of ``cholesky`` and ``hpd_solve`` on 2x2, 2x1, 1x2 and
1x4 (ISSUE 35): against a plain float64 numpy Cholesky solve on seeded
operands, and the factor's lower triangle against the form the loop had
before, which masked the whole factor at the exit, wrote a step's three
windows back together and multiplied the whole square of every trailing
window (kept below as the reference).  Since ISSUE 36 the update walks
stripes of the lower trapezoid: the sizes from ``9ib`` on have windows of
more than one stripe, a ragged last stripe and a ragged last row.

The two result tests run the program, and the reference, each as ONE
compiled program (``conftest.compiled``, ISSUE 48): walked eagerly a
nine-step case dispatched five hundred programs of its own shapes, and the
file took over a quarter of tier-1's clock.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import elemental_tpu as el
from elemental_tpu import MC, MR, STAR, VC, from_global, to_global

from ..conftest import compiled

IB = 8
HI = jax.lax.Precision.HIGHEST

_GRIDS = {"2x2": (2, 2), "2x1": (2, 1), "1x2": (1, 2), "1x4": (1, 4)}
_SIZES = {"ib": IB, "ib+1": IB + 1, "3ib": 3 * IB, "3ib+7-ragged": 3 * IB + 7,
          "5ib": 5 * IB, "9ib": 9 * IB, "9ib+5-ragged": 9 * IB + 5}
#: (lookahead, crossover): the pipelined loop with and without the
#: replicated tail, the classic order likewise
_SCHEDULES = {"lookahead-tail": (True, 2 * IB), "lookahead": (True, 0),
              "classic": (False, 0), "classic-tail": (False, 2 * IB)}


def _grid(name):
    r, c = _GRIDS[name]
    return el.Grid(jax.devices()[: r * c], height=r)


def _operand(n, dtype, seed):
    """``(F, a)``: a seeded HPD matrix in double precision, and the operand
    handed to the program: its lower triangle in ``dtype``, NaN above the
    diagonal (only the lower triangle is valid input)."""
    rng = np.random.default_rng(seed)
    G = rng.normal(size=(n, n))
    if np.issubdtype(dtype, np.complexfloating):
        G = G + 1j * rng.normal(size=(n, n))
    F = G @ G.conj().T / n + 2 * np.eye(n)
    a = (np.tril(F) + np.triu(np.full((n, n), np.nan), 1)).astype(dtype)
    return F, a


def _exit_masked_cholesky_reference(A, nb, lookahead, crossover):
    """The grid branch of ``cholesky`` as it stood before ISSUE 35, plain
    (no timer, no wire precision): the loop works in A's own shard, upper
    triangle and all, computes a step's strip and remainder from ONE
    captured L and writes the three windows back together, and masks the
    whole factor at the exit.  Each update is ONE product over the square
    window with its upper half masked away; today's stripes (ISSUE 36) are
    columns of the same products on the same operands in the same order."""
    from elemental_tpu.blas.level1 import _global_indices, make_trapezoidal
    from elemental_tpu.blas.level3 import _blocksize, _mask_triangle
    from elemental_tpu.core.distmatrix import DistMatrix
    from elemental_tpu.core.view import update_view, view
    from elemental_tpu.lapack.cholesky import _local_chol_array, _potrf_inv
    from elemental_tpu.redist.engine import panel_spread, redistribute
    g, m = A.grid, A.gshape[0]
    ib = _blocksize(nb, math.lcm(g.height, g.width), m)
    L = A

    def factor_diag(src, lo, hi):
        A11 = redistribute(view(src, rows=(lo, hi), cols=(lo, hi)),
                           STAR, STAR)
        return _potrf_inv(A11.local, HI)

    def solve_panel(src, rows, cols, Li11):
        A21 = redistribute(view(src, rows=rows, cols=cols), VC, STAR)
        x21 = jnp.matmul(A21.local, jnp.conj(Li11).T,
                         precision=HI).astype(A.dtype)
        return DistMatrix(x21, (rows[1] - rows[0], cols[1] - cols[0]),
                          VC, STAR, 0, 0, g)

    if lookahead:
        e0 = min(ib, m)
        L11, Li11 = factor_diag(L, 0, e0)
        nxt = (L11, Li11,
               solve_panel(L, (e0, m), (0, e0), Li11) if e0 < m else None)
    for s in range(0, m, ib):
        e = min(s + ib, m)
        if lookahead:
            L11, Li11, L21_vc = nxt
        else:
            L11, Li11 = factor_diag(L, s, e)
        L = update_view(L, redistribute(
            DistMatrix(L11, (e - s, e - s), STAR, STAR, 0, 0, g), MC, MR),
            rows=(s, e), cols=(s, e))
        if e == m:
            break
        if not lookahead:
            L21_vc = solve_panel(L, (e, m), (s, e), Li11)
        L21_mc, L21H_mr = panel_spread(L21_vc, conj=True)
        tail = bool(crossover) and m - e <= crossover
        if not lookahead:
            A22 = view(L, rows=(e, m), cols=(e, m))
            upd = jnp.matmul(L21_mc.local, L21H_mr.local, precision=HI)
            A22new = jnp.where(_mask_triangle(A22, "L"),
                               A22.local - upd.astype(L.dtype), A22.local)
            L = update_view(L, A22.with_local(A22new), rows=(e, m),
                            cols=(e, m))
            L = update_view(L, redistribute(L21_mc, MC, MR), rows=(e, m),
                            cols=(s, e))
        else:
            e2 = min(e + ib, m)
            A22a = view(L, rows=(e, m), cols=(e, e2))
            L21H_a = view(L21H_mr, cols=(0, e2 - e))
            stripD = A22a.with_local(jnp.where(
                _mask_triangle(A22a, "L"),
                A22a.local - jnp.matmul(L21_mc.local, L21H_a.local,
                                        precision=HI).astype(L.dtype),
                A22a.local))
            if not tail:
                L11n, Li11n = factor_diag(stripD, 0, e2 - e)
                nxt = (L11n, Li11n,
                       solve_panel(stripD, (e2 - e, m - e), (0, e2 - e),
                                   Li11n) if e2 < m else None)
            restD = None
            if e2 < m:
                A22b = view(L, rows=(e, m), cols=(e2, m))
                L21H_b = view(L21H_mr, cols=(e2 - e, m - e))
                I, J = _global_indices(A22b)
                restD = A22b.with_local(jnp.where(
                    (J[None, :] + (e2 - e)) <= I[:, None],
                    A22b.local - jnp.matmul(L21_mc.local, L21H_b.local,
                                            precision=HI).astype(L.dtype),
                    A22b.local))
            L = update_view(L, redistribute(L21_mc, MC, MR), rows=(e, m),
                            cols=(s, e))
            L = update_view(L, stripD, rows=(e, m), cols=(e, e2))
            if restD is not None:
                L = update_view(L, restD, rows=(e, m), cols=(e2, m))
        if tail:
            Atail = redistribute(view(L, rows=(e, m), cols=(e, m)),
                                 STAR, STAR)
            lt = _local_chol_array(Atail.local, m - e, ib, HI,
                                   lookahead=lookahead)
            L = update_view(L, redistribute(
                DistMatrix(lt, (m - e, m - e), STAR, STAR, 0, 0, g), MC, MR),
                rows=(e, m), cols=(e, m))
            break
    return make_trapezoidal(L, "L")


@pytest.mark.parametrize("dtype", [np.float32, np.complex64])
@pytest.mark.parametrize("schedule", list(_SCHEDULES))
@pytest.mark.parametrize("size", list(_SIZES))
@pytest.mark.parametrize("grid", list(_GRIDS))
def test_grid_cholesky_masks_at_the_entry(grid, size, schedule, dtype):
    """The factor against ``numpy.linalg.cholesky`` in double precision;
    NaN above the diagonal on input gives exact zeros there and no NaN
    anywhere on output (the operand is masked where the one working copy
    of it is made, and nothing later writes above the diagonal); the lower
    triangle equal to the exit-masked, full-square loop it replaced: to
    the bit, or, where the CPU backend's dot gives a stripe's product other
    last bits than the same columns of the full product (it picks its kernel
    by shape), within the rounding of one update's dot products of length
    ``ib``, ``ib eps |L| |L|^H`` elementwise (38 of the 224 cases, all of
    five steps or more; the largest read: 0.97 eps).  Program and reference
    each run as one compiled program; at ``3ib`` on 2x2 the eager walk of
    the program is run as well and equals the compiled one to the bit, in
    every schedule."""
    g, n = _grid(grid), _SIZES[size]
    lookahead, crossover = _SCHEDULES[schedule]
    F, a = _operand(n, dtype, seed=35 + n)
    A = from_global(a, MC, MR, grid=g)
    opts = dict(nb=IB, lookahead=lookahead, crossover=crossover)
    got = np.asarray(to_global(compiled(el.cholesky, **opts)(A)))
    assert got.dtype == dtype
    assert not np.triu(got, 1).any()
    assert np.isfinite(got).all()
    want = np.linalg.cholesky(F)
    assert np.linalg.norm(got - want) < 50 * np.finfo(dtype).eps * n \
        * np.linalg.norm(want)
    ref = np.asarray(to_global(compiled(
        _exit_masked_cholesky_reference, **opts)(A)))
    if not np.array_equal(got, ref):
        bound = IB * np.finfo(dtype).eps * (np.abs(ref) @ np.abs(ref).conj().T)
        assert (np.abs(got - ref) <= bound).all()
    if (grid, size) == ("2x2", "3ib"):
        assert np.array_equal(
            got, np.asarray(to_global(el.cholesky(A, **opts))))


@pytest.mark.parametrize("dtype", [np.float32, np.complex64])
@pytest.mark.parametrize("size", list(_SIZES))
@pytest.mark.parametrize("grid", list(_GRIDS))
def test_grid_hpd_solve_against_numpy(grid, size, dtype):
    """``hpd_solve`` (factor and both sweeps; the driver's own schedule:
    look-ahead, tail under 4096) against ``numpy.linalg.solve`` in double
    precision, three right-hand sides, NaN above A's diagonal."""
    g, n, nrhs = _grid(grid), _SIZES[size], 3
    F, a = _operand(n, dtype, seed=53 + n)
    rng = np.random.default_rng(n)
    B = rng.normal(size=(n, nrhs))
    if np.issubdtype(dtype, np.complexfloating):
        B = B + 1j * rng.normal(size=(n, nrhs))
    X = np.asarray(to_global(compiled(el.hpd_solve, nb=IB)(
        from_global(a, MC, MR, grid=g),
        from_global(B.astype(dtype), MC, MR, grid=g))))
    assert X.dtype == dtype
    want = np.linalg.solve(F, B.astype(dtype))
    eps = np.finfo(dtype).eps
    assert np.linalg.norm(X - want) < 50 * eps * n * np.linalg.norm(want)


def _eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _eqns(sub)


def _traced_factor(blocks, schedule):
    """The 2x2 factor of ``n = blocks * ib`` traced: the trace-time
    counters' registry, the jaxpr's equations in order, A's storage shape."""
    from elemental_tpu.obs import metrics
    lookahead, crossover = _SCHEDULES[schedule]
    g, n = _grid("2x2"), blocks * IB
    A = from_global(np.zeros((n, n), np.float32), MC, MR, grid=g)
    with metrics.scoped() as reg:
        jaxpr = jax.make_jaxpr(lambda a: el.cholesky(
            A.with_local(a), nb=IB, lookahead=lookahead,
            crossover=crossover).local)(A.local)
    return reg, [e for e in _eqns(jaxpr.jaxpr) if e.outvars], A.local.shape


def _assert_one_whole_select_and_it_is_first(eqns, shape):
    names = [(e.primitive.name, e.outvars[0].aval.shape) for e in eqns]
    whole = [i for i, (name, s) in enumerate(names)
             if name == "select_n" and s == shape]
    first_matmul = next(i for i, (name, _s) in enumerate(names)
                        if name == "dot_general")
    assert len(whole) == 1 and whole[0] < first_matmul, whole


@pytest.mark.parametrize("schedule", list(_SCHEDULES))
def test_grid_factor_has_one_whole_shard_select_and_it_is_first(schedule):
    """The jaxpr of the 2x2 factor holds ONE ``select_n`` over the whole
    storage, and it stands before the first matmul: the entry mask.  A
    second one at the exit was a third whole shard in the plan of
    ``hpd_solve`` at N = 65536, 4.29 GB a device (PERF.md 6, PR 35).  And
    the loop ticks ``chol_update`` once for every trailing update, the
    replicated tail's included."""
    reg, eqns, shape = _traced_factor(6, schedule)
    assert sum(reg.counters("chol_update").values()) == 6 - 1
    _assert_one_whole_select_and_it_is_first(eqns, shape)


@pytest.mark.parametrize("schedule", list(_SCHEDULES))
@pytest.mark.parametrize("blocks", [16, 32])
def test_grid_update_walks_stripes(blocks, schedule):
    """The trailing updates of the 2x2 factor of ``n = blocks * ib`` walk
    stripes of the lower trapezoid (ISSUE 36).  ``chol_update_stripe``
    ticks once for each: a window of ``j`` blocks has ``j // 2`` stripes
    ``q = 2 ib`` wide right of the look-ahead's strip, ``(j + 1) // 2`` in
    the classic order: 56 at 16 blocks and 240 at 32 with the tail at two
    (N = 32768 and 65536 at ib = 2048, crossover 4096).  The matmuls under
    ``update`` multiply at most 0.61 (0.56) of what full squares of the
    windows take (0.605 and 0.550 with look-ahead and tail: the stripes'
    own 0.588 and 0.545, and the block row above the corner stripe).
    Every one of them is taller than wide wherever the window allows it,
    which is what keeps the working shard column-major on the chip.
    ``chol_update`` still ticks once a step, and the entry mask is still
    the one whole ``select_n``."""
    lookahead, crossover = _SCHEDULES[schedule]
    reg, eqns, shape = _traced_factor(blocks, schedule)
    assert sum(reg.counters("chol_update").values()) == blocks - 1
    windows = range(2 if crossover else 1, blocks)   # j, in blocks
    stripes = sum(j // 2 if lookahead else (j + 1) // 2 for j in windows)
    if schedule == "lookahead-tail":
        assert stripes == {16: 56, 32: 240}[blocks]
    assert sum(reg.counters("chol_update_stripe").values()) == stripes
    _assert_one_whole_select_and_it_is_first(eqns, shape)
    # (M, K) @ (K, N) of every matmul under a step's ``update`` (the
    # replicated tail's are ``k<step>/tail/k<step>/update``: another buffer)
    scopes = ((e, str(e.source_info.name_stack)) for e in eqns
              if e.primitive.name == "dot_general")
    dots = [(*e.invars[0].aval.shape, e.invars[1].aval.shape[1])
            for e, scope in scopes
            if scope.endswith("/update") and "/tail/" not in scope]
    flops = sum(2 * M * K * N for M, K, N in dots)
    squares = sum(2 * (j * IB) ** 2 * IB for j in windows)
    assert flops <= {16: 0.61, 32: 0.56}[blocks] * squares, flops / squares
    # as wide as tall only where the window has no block row above the
    # stripe: a whole window of one stripe (classic), a strip ib tall
    assert all(M > N or M <= 2 * IB for M, K, N in dots), dots
    if schedule == "lookahead-tail":
        assert all(M > N for M, K, N in dots), dots
