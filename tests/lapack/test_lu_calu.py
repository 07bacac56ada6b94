"""CALU tournament-pivoted LU (ISSUE 6): validity, stability vs the
classic partial-pivot baseline, and round-trip coverage.

CALU's pivots come from a log-depth tournament over grid-row slabs, not
from a global per-column argmax, so its growth factor bound is weaker
than partial pivoting's (2^{b log r}-class instead of 2^k-class, cf.
Grigori/Demmel/Xiang).  The suite certifies the residual anyway: on the
random / graded / Wilkinson-adversarial stability matrices the backward
error ``||P A - L U|| / ||A||`` must stay within a documented factor of
classic's (and near roundoff in absolute terms) -- the factorization is
algebra-exact for ANY row choice; what the bound guards is growth in the
factors feeding the solve path.
"""
import numpy as np
import pytest

import elemental_tpu as el
from elemental_tpu import MC, MR, from_global, to_global
from elemental_tpu.lapack.lu import lu, lu_solve, lu_solve_after, permute_rows

from ..conftest import compiled

#: documented stability bound: calu residual may exceed classic's by at
#: most this factor (plus an absolute roundoff floor) on the suite below.
#: The theoretical growth ratio is 2^{b(log2 r)} worst-case; on these
#: matrices the observed ratio is O(1) -- the margin catches a broken
#: tournament (wrong winners => catastrophic growth), not noise.
CALU_RESIDUAL_FACTOR = 64.0
_FLOOR = 1e-14


def _dist(g, arr):
    return from_global(arr, MC, MR, grid=g)


def _unpack(LUh):
    m, n = LUh.shape
    k = min(m, n)
    L = np.tril(LUh[:, :k], -1) + np.eye(m, k)
    U = np.triu(LUh[:k, :])
    return L, U


def _resid(F, LUd, perm):
    LUh = np.asarray(to_global(LUd))
    L, U = _unpack(LUh)
    p = np.asarray(perm)
    assert sorted(p.tolist()) == list(range(F.shape[0]))
    return np.linalg.norm(F[p, :] - L @ U) / np.linalg.norm(F)


# ---------------------------------------------------------------------
# validity: PA = LU across shapes / schedules
# ---------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(24, 24), (32, 20), (20, 32), (19, 19),
                                   (19, 32), (32, 19), (18, 30)])
def test_calu_residual(grid24, shape):
    m, n = shape
    rng = np.random.default_rng(61)
    F = rng.normal(size=(m, n))
    LUd, perm = compiled(lu, nb=8, panel="calu")(_dist(grid24, F))
    assert _resid(F, LUd, perm) < 1e-13


def test_calu_lookahead_matches_classic_schedule(grid24):
    """The pipelined schedule reorders ops, not math: calu pivots and
    factors agree between lookahead and classic schedules (crossover
    disabled so both run the full distributed loop)."""
    rng = np.random.default_rng(62)
    F = rng.normal(size=(32, 32))
    A = _dist(grid24, F)
    LUa, pa = compiled(lu, nb=8, panel="calu", lookahead=True,
                       crossover=0)(A)
    LUb, pb = compiled(lu, nb=8, panel="calu", lookahead=False)(A)
    np.testing.assert_array_equal(np.asarray(pa), np.asarray(pb))
    np.testing.assert_allclose(np.asarray(to_global(LUa)),
                               np.asarray(to_global(LUb)),
                               rtol=1e-12, atol=1e-12)


@pytest.mark.parametrize("xo", [0, 16, 10_000])
def test_calu_crossover_tail_valid(grid24, xo):
    """The crossover tail finishes with the local classic kernel, so the
    pivot SET differs from pure calu past the tail boundary -- but the
    factorization must stay residual-exact at every threshold."""
    rng = np.random.default_rng(63)
    F = rng.normal(size=(48, 48))
    LUd, perm = compiled(lu, nb=8, panel="calu", lookahead=True,
                         crossover=xo)(_dist(grid24, F))
    assert _resid(F, LUd, perm) < 1e-13


def test_calu_degenerates_to_classic_on_single_row_grid():
    """One grid row: the slab IS the panel, the tournament IS partial
    pivoting -- pivots and factors must match classic exactly."""
    import jax
    g18 = el.Grid(jax.devices(), height=1)
    rng = np.random.default_rng(64)
    F = rng.normal(size=(24, 24))
    A = _dist(g18, F)
    LUa, pa = compiled(lu, nb=8, panel="calu", lookahead=False)(A)
    LUb, pb = compiled(lu, nb=8, panel="classic", lookahead=False)(A)
    np.testing.assert_array_equal(np.asarray(pa), np.asarray(pb))
    np.testing.assert_allclose(np.asarray(to_global(LUa)),
                               np.asarray(to_global(LUb)),
                               rtol=1e-13, atol=1e-13)


# ---------------------------------------------------------------------
# stability suite: random / graded (ill-conditioned) / Wilkinson-adversarial
# ---------------------------------------------------------------------

def _stability_cases(n):
    rng = np.random.default_rng(65)
    random = rng.normal(size=(n, n))
    # graded: geometrically scaled rows+cols, cond ~ 1e12
    grade = np.logspace(0, -6, n)
    graded = grade[:, None] * rng.normal(size=(n, n)) * grade[None, :]
    # Wilkinson growth matrix: partial pivoting never swaps and the last
    # column doubles every step (growth 2^{n-1}); a tournament that picks
    # bad rows here blows the residual up immediately
    wilk = np.eye(n) + np.tril(-np.ones((n, n)), -1)
    wilk[:, -1] = 1.0
    return [("random", random), ("graded", graded), ("wilkinson", wilk)]


@pytest.mark.parametrize("case", ["random", "graded", "wilkinson"])
def test_calu_stability_vs_classic(grid24, case):
    n = 32
    F = dict(_stability_cases(n))[case]
    A = _dist(grid24, F)
    LUc, pc = compiled(lu, nb=8, panel="classic", lookahead=False)(A)
    LUt, pt = compiled(lu, nb=8, panel="calu", lookahead=False)(A)
    r_classic = _resid(F, LUc, pc)
    r_calu = _resid(F, LUt, pt)
    assert r_calu <= CALU_RESIDUAL_FACTOR * r_classic + _FLOOR, (
        case, r_calu, r_classic)


# ---------------------------------------------------------------------
# solve / permutation round trips with tournament permutations
# ---------------------------------------------------------------------

def test_calu_lu_solve(grid24):
    n, nrhs = 24, 4
    rng = np.random.default_rng(66)
    F = rng.normal(size=(n, n)) + n * np.eye(n)
    B = rng.normal(size=(n, nrhs))
    X = compiled(lu_solve, nb=8, panel="calu")(_dist(grid24, F),
                                               _dist(grid24, B))
    Xh = np.asarray(to_global(X))
    assert np.linalg.norm(F @ Xh - B) / np.linalg.norm(B) < 1e-12


def test_calu_lu_solve_after_reuse(grid24):
    n = 24
    rng = np.random.default_rng(67)
    F = rng.normal(size=(n, n)) + n * np.eye(n)
    LUd, perm = compiled(lu, nb=8, panel="calu")(_dist(grid24, F))
    solve_after = compiled(lu_solve_after, nb=8)
    for seed in (1, 2):
        B = np.random.default_rng(seed).normal(size=(n, 2))
        X = solve_after(LUd, perm, _dist(grid24, B))
        assert np.linalg.norm(F @ np.asarray(to_global(X)) - B) \
            < 1e-12 * np.linalg.norm(B)


def test_calu_permute_rows_inverse_roundtrip(grid24):
    """permute_rows(inverse=True) undoes a tournament permutation (the
    engine's storage-level one-shot fast path on both directions)."""
    n = 24
    rng = np.random.default_rng(68)
    F = rng.normal(size=(n, n))
    B = rng.normal(size=(n, 5))
    _, perm = compiled(lu, nb=8, panel="calu")(_dist(grid24, F))
    Bd = _dist(grid24, B)
    Bp = permute_rows(Bd, perm)
    np.testing.assert_allclose(np.asarray(to_global(Bp)),
                               B[np.asarray(perm), :], rtol=1e-14)
    back = permute_rows(Bp, perm, inverse=True)
    np.testing.assert_allclose(np.asarray(to_global(back)), B, rtol=1e-14)


# ---------------------------------------------------------------------
# knob plumbing + obs
# ---------------------------------------------------------------------

def test_calu_rejects_unknown_panel(grid24):
    rng = np.random.default_rng(69)
    F = rng.normal(size=(16, 16))
    with pytest.raises(ValueError, match="panel"):
        lu(_dist(grid24, F), nb=8, panel="tournament")


def test_calu_tournament_phase_tick(grid24):
    """The tournament phase is observable: an eager run with a timer hook
    sees 'tournament' ticks between pivot selection and the unpivoted
    panel refactorization (ISSUE 6's obs rider)."""
    class Hook:
        def __init__(self):
            self.phases = []

        def start(self):
            pass

        def tick(self, phase, step, *arrays):
            self.phases.append(str(phase))

    rng = np.random.default_rng(70)
    F = rng.normal(size=(32, 32))
    hook = Hook()
    lu(_dist(grid24, F), nb=8, panel="calu", crossover=0, timer=hook)
    assert "tournament" in hook.phases
    assert "panel" in hook.phases and "solve" in hook.phases
    # classic never ticks the tournament phase
    hook2 = Hook()
    lu(_dist(grid24, F), nb=8, panel="classic", crossover=0, timer=hook2)
    assert "tournament" not in hook2.phases
