"""The QR-based QDWH step's route over its stack's structure (ISSUE 54).

``qr._stack_qr_thin_q(X, sc, nb)`` gives the thin Q of ``[sc X; I]`` as
``(Q1, Q2)`` over the rows and columns that are not structurally zero.
Each case is ONE compiled program: against the general ``qr`` +
``apply_q`` on the explicit stack (the same reflectors, so a few ulp),
and against float64 numpy (``[Q1; Q2]`` orthonormal, the stack in its
range, ``Q2`` upper triangular EXACTLY); square and tall, a ragged last
panel, one panel only, float64 and complex64, one device and the 2x2
mesh, a grid whose grain the cut rows miss; the counter and how often a
panel's T is built.
"""
import importlib

import jax
import numpy as np
import pytest

import elemental_tpu as el
from elemental_tpu import obs
from elemental_tpu.blas.level1 import shift_diagonal
from elemental_tpu.lapack.qr import _stack_qr_thin_q, apply_q, qr
from elemental_tpu.redist.interior import _blank, interior_view, vstack

#: the module: ``elemental_tpu.lapack.qr`` the attribute is the function
qr_mod = importlib.import_module("elemental_tpu.lapack.qr")
SC = 3.7
#: name -> (rows, cols, nb, dtype)
CASES = {"square64": (64, 64, 16, np.float32),
         "tall80x48": (80, 48, 16, np.float32),
         "ragged50x37": (50, 37, 16, np.float32),
         "one_panel32": (32, 32, 64, np.float32),
         "float64": (64, 64, 16, np.float64),
         "complex64": (48, 48, 16, np.complex64)}
#: on 2x2 ``odd63`` misses the grain: m + e is odd for every panel but
#: the last
GRID_CASES = {"square64": (64, 64, 16, np.float32),
              "tall70x50": (70, 50, 16, np.float32),
              "odd63": (63, 63, 16, np.float32),
              "complex64": (48, 48, 16, np.complex64)}


def _grid(name):
    r, c = (int(d) for d in name.split("x"))
    return el.Grid(list(jax.devices()[:r * c]), height=r)


def _operand(m, n, dtype):
    rng = np.random.default_rng(54 + m + n)
    F = rng.uniform(-1, 1, size=(m, n))
    if np.issubdtype(dtype, np.complexfloating):
        F = F + 1j * rng.uniform(-1, 1, size=(m, n))
    return F.astype(dtype)


def _general(X, nb):
    """The thin Q by the general route: ``qr`` of the explicit stack and
    ``apply_q`` on an explicit ``[I; 0]``."""
    m, n = X.gshape
    S = vstack(X.with_local(SC * X.local), shift_diagonal(_blank(n, n, X), 1))
    Ap, tau = qr(S, nb=nb)
    Q = apply_q(Ap, tau, shift_diagonal(_blank(m + n, n, X), 1), nb=nb)
    return (interior_view(Q, (0, m), (0, n)),
            interior_view(Q, (m, m + n), (0, n)))


def _both(F, grid, nb):
    """``(Q1, Q2)`` of the structured route and of the general one as numpy,
    each from one compiled call, and the registry the first ticked."""
    X = el.from_global(F, el.MC, el.MR, grid=grid)
    with obs.metrics_scope() as reg:
        ours = jax.jit(lambda x: _stack_qr_thin_q(x, SC, nb=nb))(X)
    assert reg.counter_value("compile_requests") == 1
    theirs = jax.jit(lambda x: _general(x, nb))(X)
    return ([np.asarray(el.to_global(Q)) for Q in ours],
            [np.asarray(el.to_global(Q)) for Q in theirs], reg)


def _check(F, Q1, Q2, G1, G2):
    m, n = F.shape
    eps = float(np.finfo(F.dtype).eps)
    assert Q1.shape == (m, n) and Q2.shape == (n, n)
    assert Q1.dtype == F.dtype and Q2.dtype == F.dtype
    # the same reflectors applied to fewer zeros: a few ulp of entries
    # that are at most 1
    assert np.abs(Q1 - G1).max() <= 8 * eps
    assert np.abs(Q2 - G2).max() <= 8 * eps
    # rows below a column's panel are never written
    assert not np.tril(Q2, -1).any()
    wide = np.complex128 if np.iscomplexobj(F) else np.float64
    Q = np.vstack([Q1, Q2]).astype(wide)
    S = np.vstack([SC * F.astype(wide), np.eye(n)])
    assert np.linalg.norm(Q.conj().T @ Q - np.eye(n)) <= 20 * eps * np.sqrt(n)
    # [Q1; Q2] R = S for R = Q^H S: the stack lies in Q's range, and R is
    # upper triangular
    R = Q.conj().T @ S
    assert np.linalg.norm(Q @ R - S) <= 20 * eps * np.linalg.norm(S)
    assert np.linalg.norm(np.tril(R, -1)) <= 20 * eps * np.linalg.norm(S)


@pytest.mark.parametrize("case", CASES)
def test_structured_route_on_one_device(case):
    m, n, nb, dtype = CASES[case]
    F = _operand(m, n, dtype)
    (Q1, Q2), (G1, G2), reg = _both(F, _grid("1x1"), nb)
    _check(F, Q1, Q2, G1, G2)
    assert dict(reg.counters("qdwh_stack_qr")) == {
        ("qdwh_stack_qr", (("route", "structured"),)): 1}


@pytest.mark.parametrize("case", GRID_CASES)
def test_structured_route_on_the_2x2_mesh(case):
    """On a grid a row range ends on the grain: with m odd every panel but
    the last keeps all its rows, and the route says ``dense``."""
    m, n, nb, dtype = GRID_CASES[case]
    F = _operand(m, n, dtype)
    (Q1, Q2), (G1, G2), reg = _both(F, _grid("2x2"), nb)
    _check(F, Q1, Q2, G1, G2)
    route = "dense" if m % 2 else "structured"
    assert dict(reg.counters("qdwh_stack_qr")) == {
        ("qdwh_stack_qr", (("route", route),)): 1}


@pytest.mark.parametrize("grid_name", ["1x1", "2x2"])
def test_a_panels_t_is_built_once(grid_name, monkeypatch):
    """``qr`` built a panel's T for its update and ``apply_q`` built it
    again: the structured route keeps it."""
    calls = []
    larft = qr_mod._larft
    monkeypatch.setattr(qr_mod, "_larft",
                        lambda V, tau: calls.append(V.shape) or larft(V, tau))
    m, n, nb = 64, 64, 16
    X = el.from_global(_operand(m, n, np.float32), el.MC, el.MR,
                       grid=_grid(grid_name))
    jax.jit(lambda x: _stack_qr_thin_q(x, SC, nb=nb)).lower(X)
    # every panel has m + nb rows, whatever its offset
    assert calls == [(m + nb, nb)] * (n // nb)
    calls.clear()
    jax.jit(lambda x: _general(x, nb)).lower(X)
    assert len(calls) == 2 * (n // nb) - 1      # the last panel: no update


@pytest.mark.parametrize("grid_name", ["1x1", "2x2"])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_polar_ticks_once_a_qr_based_step(grid_name, dtype):
    """``polar``'s QR-based steps take the structured route, each ticking
    once (two in either schedule; they are one loop body, and a counter
    under it is weighed by the trips), and U is a polar factor."""
    n = 64
    F = _operand(n, n, dtype)
    A = el.from_global(F, el.MC, el.MR, grid=_grid(grid_name))
    with obs.metrics_scope() as reg:
        U, H = jax.jit(lambda a: el.polar(a, nb=16))(A)
    assert reg.counter_value("qdwh_step", kind="qr") == 2
    assert dict(reg.counters("qdwh_stack_qr")) == {
        ("qdwh_stack_qr", (("route", "structured"),)): 2}
    U = np.asarray(el.to_global(U), np.float64)
    H = np.asarray(el.to_global(H), np.float64)
    tol = 100 * float(np.finfo(dtype).eps)
    assert np.linalg.norm(U.T @ U - np.eye(n)) <= tol * np.sqrt(n)
    assert np.linalg.norm(U @ H - F) <= tol * np.linalg.norm(F)


def test_a_wide_upper_block_is_refused():
    X = el.from_global(_operand(16, 32, np.float32), el.MC, el.MR,
                       grid=_grid("1x1"))
    with pytest.raises(ValueError, match="must be tall"):
        _stack_qr_thin_q(X, SC, nb=16)
