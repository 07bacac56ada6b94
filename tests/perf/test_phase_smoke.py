"""Perf-observability smoke: tiny LU/Cholesky through the phase-timing hook.

Slow-tier guard for the ``elemental_tpu.obs.PhaseTimer`` + ``lu/cholesky(...,
timer=...)`` paths (ISSUE 1/2 CI satellites): asserts the
``phase_timings/v1`` JSON schema so the attribution tooling future perf
PRs rely on cannot silently rot.
"""
import json

import numpy as np
import pytest

import elemental_tpu as el

pytestmark = pytest.mark.slow


def _check_schema(doc, n, nb, nsteps):
    from elemental_tpu.obs.phase_timer import SCHEMA, PHASES
    assert doc["schema"] == SCHEMA
    assert doc["driver"] == "lu"
    assert doc["n"] == n and doc["nb"] == nb
    steps = doc["steps"]
    assert [s["step"] for s in steps] == list(range(nsteps))
    for srec in steps:
        phases = set(srec) - {"step"}
        assert phases <= set(PHASES)
        assert "panel" in phases and "swap" in phases
        for p in phases:
            assert isinstance(srec[p], float) and srec[p] >= 0.0
    totals = doc["totals"]
    assert set(totals) <= set(PHASES) and "panel" in totals
    assert doc["total_seconds"] >= sum(totals.values()) - 1e-9
    json.dumps(doc)          # round-trippable


@pytest.mark.parametrize("lookahead", [True, False])
def test_lu_phase_timer_schema_distributed(grid24, lookahead):
    from elemental_tpu.obs import PhaseTimer
    n, nb = 48, 16
    rng = np.random.default_rng(0)
    F = rng.normal(size=(n, n)) + n * np.eye(n)
    A = el.from_global(F, el.MC, el.MR, grid=grid24)
    t = PhaseTimer()
    LU, perm = el.lu(A, nb=nb, lookahead=lookahead, crossover=0, timer=t)
    doc = json.loads(t.json(driver="lu", n=n, nb=nb, lookahead=lookahead))
    _check_schema(doc, n, nb, nsteps=n // nb)
    # the timed run is still a correct factorization
    LUh = np.asarray(el.to_global(LU))
    L = np.tril(LUh, -1) + np.eye(n)
    U = np.triu(LUh)
    p = np.asarray(perm)
    assert np.linalg.norm(F[p, :] - L @ U) < 1e-11 * np.linalg.norm(F)


def test_lu_phase_timer_schema_local():
    """Same schema off the sequential (1x1-grid) driver."""
    import jax
    from elemental_tpu.obs import PhaseTimer
    g1 = el.Grid([jax.devices()[0]])
    n, nb = 64, 16
    rng = np.random.default_rng(1)
    F = rng.normal(size=(n, n)) + n * np.eye(n)
    A = el.from_global(F, el.MC, el.MR, grid=g1)
    t = PhaseTimer()
    LU, perm = el.lu(A, nb=nb, timer=t)
    doc = json.loads(t.json(driver="lu", n=n, nb=nb))
    _check_schema(doc, n, nb, nsteps=n // nb)


def test_lu_phase_timer_tail_crossover(grid24):
    """The LU crossover step attributes its gathered local finish to
    'tail' (the ISSUE-3 rider mirroring the cholesky PR-2 tail)."""
    from elemental_tpu.obs import PhaseTimer
    n, nb = 48, 16
    rng = np.random.default_rng(7)
    F = rng.normal(size=(n, n)) + n * np.eye(n)
    A = el.from_global(F, el.MC, el.MR, grid=grid24)
    t = PhaseTimer()
    LU, perm = el.lu(A, nb=nb, crossover=nb, timer=t)
    doc = json.loads(t.json(driver="lu", n=n, nb=nb))
    # steps 0 and 1 run distributed; the 16-wide tail crosses over at step 1
    steps = doc["steps"]
    assert [s["step"] for s in steps] == [0, 1]
    assert "tail" in steps[-1] and "tail" in doc["totals"]
    LUh = np.asarray(el.to_global(LU))
    L = np.tril(LUh, -1) + np.eye(n)
    U = np.triu(LUh)
    p = np.asarray(perm)
    assert np.linalg.norm(F[p, :] - L @ U) < 1e-11 * np.linalg.norm(F)


def _spd(n, seed):
    rng = np.random.default_rng(seed)
    G = rng.normal(size=(n, n))
    return G @ G.T / n + n * np.eye(n)


def _check_chol_schema(doc, n, nb, nsteps, tail=False):
    from elemental_tpu.obs.phase_timer import SCHEMA, PHASES
    assert doc["schema"] == SCHEMA
    assert doc["driver"] == "cholesky"
    assert doc["n"] == n and doc["nb"] == nb
    steps = doc["steps"]
    assert [s["step"] for s in steps] == list(range(nsteps))
    for srec in steps:
        phases = set(srec) - {"step"}
        assert phases <= set(PHASES)
        assert "diag" in phases
        for p in phases:
            assert isinstance(srec[p], float) and srec[p] >= 0.0
    totals = doc["totals"]
    assert set(totals) <= set(PHASES) and "diag" in totals
    assert ("tail" in totals) == tail
    assert doc["total_seconds"] >= sum(totals.values()) - 1e-9
    json.dumps(doc)          # round-trippable


@pytest.mark.parametrize("lookahead", [True, False])
def test_cholesky_phase_timer_schema_distributed(grid24, lookahead):
    from elemental_tpu.obs import PhaseTimer
    n, nb = 48, 16
    F = _spd(n, 2)
    A = el.from_global(F, el.MC, el.MR, grid=grid24)
    t = PhaseTimer()
    L = el.cholesky(A, nb=nb, lookahead=lookahead, crossover=0, timer=t)
    doc = json.loads(t.json(driver="cholesky", n=n, nb=nb,
                            lookahead=lookahead))
    _check_chol_schema(doc, n, nb, nsteps=n // nb)
    # non-final steps must also carry the panel/spread/update phases
    for srec in doc["steps"][:-1]:
        assert {"panel", "spread", "update"} <= set(srec)
    # the timed run is still a correct factorization
    Lh = np.asarray(el.to_global(L))
    assert np.linalg.norm(F - Lh @ Lh.T) < 1e-11 * np.linalg.norm(F)


def test_cholesky_phase_timer_tail_crossover(grid24):
    """The crossover step attributes its gathered local finish to 'tail'."""
    from elemental_tpu.obs import PhaseTimer
    n, nb = 48, 16
    F = _spd(n, 3)
    A = el.from_global(F, el.MC, el.MR, grid=grid24)
    t = PhaseTimer()
    L = el.cholesky(A, nb=nb, crossover=nb, timer=t)
    doc = json.loads(t.json(driver="cholesky", n=n, nb=nb))
    # steps 0 and 1 run distributed; the 16-wide tail crosses over at step 1
    _check_chol_schema(doc, n, nb, nsteps=2, tail=True)
    assert "tail" in doc["steps"][-1]
    Lh = np.asarray(el.to_global(L))
    assert np.linalg.norm(F - Lh @ Lh.T) < 1e-11 * np.linalg.norm(F)


def test_cholesky_phase_timer_schema_local():
    """Same schema off the sequential (1x1-grid) driver."""
    import jax
    from elemental_tpu.obs import PhaseTimer
    g1 = el.Grid([jax.devices()[0]])
    n, nb = 64, 16
    F = _spd(n, 4)
    A = el.from_global(F, el.MC, el.MR, grid=g1)
    t = PhaseTimer()
    L = el.cholesky(A, nb=nb, timer=t)
    doc = json.loads(t.json(driver="cholesky", n=n, nb=nb))
    _check_chol_schema(doc, n, nb, nsteps=n // nb)
