"""HPL's deployment on a process grid, at N = 256 on the CPU mesh: the
pivoted LU solve on 2x2 on the benchmark's own ``uniform_pm1`` operand
against a plain float64 LU written here, the storage-level row
permutations behind its swaps, what one panel step asks of the wire
(counters), and the Cholesky solve against many right-hand sides
(the cells ``lu16k.2x2.b2b`` and ``hpd32k.1x1.rhs4096``, ISSUE 31)."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import elemental_tpu as el
from elemental_tpu import MC, MR, from_global, to_global
from elemental_tpu.lapack.lu import lu, lu_solve, lu_solve_after
from elemental_tpu.obs import metrics_scope
from elemental_tpu.redist import engine

from ..conftest import compiled

N, NB = 256, 64
EPS32 = float(np.finfo(np.float32).eps)


@pytest.fixture(scope="module")
def reference():
    """``benchmark/reference.py`` (plain ``jax.numpy``, nothing of the
    program), loaded by path: the operand every cell is generated from."""
    path = os.path.join(os.path.dirname(__file__), os.pardir, os.pardir,
                        "benchmark", "reference.py")
    spec = importlib.util.spec_from_file_location("benchmark_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _operand(reference, name, rows, cols, which, seed=31):
    key = jnp.uint32(reference.operand_key(seed, 0, which))
    block = reference.plain_block(reference.ENTRIES[name](N, key), 0, rows,
                                  cols)
    return np.asarray(block, np.float32)


def _grid(r, c):
    return el.Grid(jax.devices()[:r * c], height=r)


def plain_lu(A):
    """Right-looking LU with row partial pivoting in float64, one column at
    a time; the pivot is the FIRST largest |entry| of the column.  Returns
    (packed L\\U, perm) with ``A[perm] = L U``."""
    a = np.array(A, np.float64)
    n = a.shape[0]
    perm = np.arange(n)
    for j in range(n):
        p = j + int(np.argmax(np.abs(a[j:, j])))
        a[[j, p]] = a[[p, j]]
        perm[[j, p]] = perm[[p, j]]
        a[j + 1:, j] /= a[j, j]
        a[j + 1:, j + 1:] -= np.outer(a[j + 1:, j], a[j, j + 1:])
    return a, perm


def plain_lu_solve(A, B):
    a, perm = plain_lu(A)
    n = a.shape[0]
    y = np.array(B, np.float64)[perm]
    for j in range(n):                       # unit lower
        y[j + 1:] -= np.outer(a[j + 1:, j], y[j])
    for j in range(n - 1, -1, -1):           # upper
        y[j] /= a[j, j]
        y[:j] -= np.outer(a[:j, j], y[j])
    return y, perm


def _backward_error(A, X, B):
    A, X, B = (np.asarray(v, np.float64) for v in (A, X, B))
    return np.linalg.norm(B - A @ X) / (
        np.linalg.norm(A) * np.linalg.norm(X) + np.linalg.norm(B))


# ---------------------------------------------------------------------
# the pivoted solve on the grid
# ---------------------------------------------------------------------

def _solve(grid, A, B, crossover):
    """(pivot vector, X) of the public path on a grid.  ``'driver'`` is
    ``lu_solve`` itself (what the cell calls: the default crossover, which
    at this size finishes the factor in the replicated tail after step 0);
    a number is the same two stages with that crossover."""
    Ad, Bd = (from_global(v, MC, MR, grid=grid) for v in (A, B))
    kwargs = {} if crossover == "driver" else {"crossover": crossover}
    LU_, perm = compiled(lu, nb=NB, **kwargs)(Ad)
    if crossover == "driver":
        X = compiled(lu_solve, nb=NB)(Ad, Bd)
    else:
        X = compiled(lu_solve_after, nb=NB)(LU_, perm, Bd)
    return np.asarray(perm), np.asarray(to_global(X))


@pytest.mark.parametrize("nrhs", [1, 8])
@pytest.mark.parametrize("crossover", [
    pytest.param("driver", id="driver-default-tail"),
    pytest.param(128, id="two-steps-then-tail"),
    pytest.param(0, id="no-tail"),
])
def test_lu_solve_on_2x2_has_hpls_pivots_and_answer(reference, crossover,
                                                    nrhs):
    A = _operand(reference, "uniform_pm1", N, N, 0)
    B = _operand(reference, "uniform_pm1", N, nrhs, 1)
    want_x, want_perm = plain_lu_solve(A, B)

    perm22, X22 = _solve(_grid(2, 2), A, B, crossover)
    perm11, X11 = _solve(_grid(1, 1), A, B, crossover)
    np.testing.assert_array_equal(perm22, want_perm)
    np.testing.assert_array_equal(perm11, want_perm)
    assert X22.dtype == np.float32 and X22.shape == (N, nrhs)
    for X in (X22, X11):
        assert _backward_error(A, X, B) < 50 * EPS32 * N
    # the answer itself: a forward error the operand's condition allows
    assert np.linalg.norm(X22 - want_x) <= 1e-2 * np.linalg.norm(want_x)


# ---------------------------------------------------------------------
# the storage-level row permutations
# ---------------------------------------------------------------------

GRIDS = [pytest.param(2, 2, id="2x2"), pytest.param(2, 1, id="2x1"),
         pytest.param(1, 2, id="1x2")]


@pytest.mark.parametrize("shape", [(256, 256), (256, 1), (37, 19)],
                         ids=lambda s: f"{s[0]}x{s[1]}")
@pytest.mark.parametrize("r,c", GRIDS)
def test_permute_rows_storage_is_bit_equal_to_indexing(r, c, shape):
    rng = np.random.default_rng(7)
    F = rng.standard_normal(shape).astype(np.float32)
    perm = rng.permutation(shape[0])
    A = from_global(F, MC, MR, grid=_grid(r, c))
    got = to_global(engine.permute_rows_storage(A, jnp.asarray(perm)))
    np.testing.assert_array_equal(np.asarray(got), F[perm])
    back = to_global(engine.permute_rows_storage(
        engine.permute_rows_storage(A, jnp.asarray(perm)),
        jnp.asarray(perm), inverse=True))
    np.testing.assert_array_equal(np.asarray(back), F)


@pytest.mark.parametrize("shape", [(256, 256), (256, 1), (37, 19)],
                         ids=lambda s: f"{s[0]}x{s[1]}")
@pytest.mark.parametrize("r,c", GRIDS)
def test_move_rows_is_bit_equal_to_indexing(r, c, shape):
    """A panel step's swaps: some rows exchanged in pairs, padded with
    invalid sentinel entries that must move nothing."""
    m = shape[0]
    rng = np.random.default_rng(11)
    F = rng.standard_normal(shape).astype(np.float32)
    pairs = rng.permutation(m)[:2 * (m // 5)].reshape(2, -1)
    perm = np.arange(m)
    perm[pairs[0]], perm[pairs[1]] = pairs[1], pairs[0]
    moved = np.flatnonzero(perm != np.arange(m))
    pad = 5
    targets = np.concatenate([moved, np.full(pad, m + 3)])
    sources = np.concatenate([perm[moved], np.full(pad, m + 3)])
    valid = np.concatenate([np.ones(moved.size, bool), np.zeros(pad, bool)])
    A = from_global(F, MC, MR, grid=_grid(r, c))
    got = to_global(engine.move_rows(A, jnp.asarray(targets),
                                     jnp.asarray(sources),
                                     jnp.asarray(valid)))
    np.testing.assert_array_equal(np.asarray(got), F[perm])


# ---------------------------------------------------------------------
# what a panel step asks of the wire
# ---------------------------------------------------------------------

def _row_permute_counters(fn):
    # the counters tick where an entry is traced: these calls are eager,
    # so every call runs the entry's Python
    with metrics_scope() as reg:
        fn()
    return {name + "." + dict(labels)["kind"]: value
            for name in ("row_permute", "row_permute_rows",
                         "row_permute_wire_bytes")
            for (_, labels), value in reg.counters(name).items()}


def test_row_permute_counters_of_one_panel_step():
    """One ``move_rows`` of a panel step at nb = 64 on 2x2: 2 nb rows asked
    to move (the pivots and the rows they displace), each the stacked
    storage's full width, float32."""
    A = from_global(np.ones((N, N), np.float32), MC, MR, grid=_grid(2, 2))
    k = 2 * NB
    idx = jnp.arange(k)
    got = _row_permute_counters(
        lambda: engine.move_rows(A, idx, idx[::-1], idx < k))
    assert got == {"row_permute.move": 1, "row_permute_rows.move": k,
                   "row_permute_wire_bytes.move": k * N * 4}


def test_row_permute_counters_of_the_grid_solve(reference):
    """The whole ``lu_solve`` of the cell's shape in miniature (N = 256,
    nb = 64, nrhs = 1, the driver's default crossover, which at this size
    ends in the tail after step 0): one panel step's swaps, the tail's one
    permutation of the rows below it, and the permutation of B, whose
    stacked storage on a grid two columns wide is N x 2."""
    A = _operand(reference, "uniform_pm1", N, N, 0)
    B = _operand(reference, "uniform_pm1", N, 1, 1)
    grid = _grid(2, 2)
    Ad, Bd = (from_global(v, MC, MR, grid=grid) for v in (A, B))
    got = _row_permute_counters(lambda: lu_solve(Ad, Bd, nb=NB))
    assert got == {
        "row_permute.move": 2,
        "row_permute_rows.move": 2 * NB + (N - NB),
        "row_permute_wire_bytes.move": (2 * NB + (N - NB)) * N * 4,
        "row_permute.full": 1,
        "row_permute_rows.full": N,
        "row_permute_wire_bytes.full": N * 2 * 4}


def test_one_chip_counts_its_swaps_too():
    """On 1x1 the same entries are traced (nothing crosses a wire there:
    the figure is the bound for a grid, and the benchmark reads it only
    across chips)."""
    A = from_global(np.ones((N, N), np.float32), MC, MR, grid=_grid(1, 1))
    got = _row_permute_counters(
        lambda: engine.permute_rows_storage(A, jnp.arange(N)[::-1]))
    assert got == {"row_permute.full": 1, "row_permute_rows.full": N,
                   "row_permute_wire_bytes.full": N * N * 4}


# ---------------------------------------------------------------------
# the Cholesky solve against many right-hand sides
# ---------------------------------------------------------------------

@pytest.mark.parametrize("nrhs", [N // 8, N], ids=["nrhs-n/8", "nrhs-n"])
@pytest.mark.parametrize("r,c", [pytest.param(1, 1, id="1x1"),
                                 pytest.param(2, 2, id="2x2")])
def test_hpd_solve_with_many_right_hand_sides(reference, r, c, nrhs):
    A = _operand(reference, "hpd_shifted", N, N, 0)
    B = _operand(reference, "uniform_pm1", N, nrhs, 1)
    grid = _grid(r, c)
    X = compiled(el.hpd_solve, nb=NB)(from_global(A, MC, MR, grid=grid),
                                      from_global(B, MC, MR, grid=grid))
    X = np.asarray(to_global(X))
    want = np.linalg.solve(A.astype(np.float64), B.astype(np.float64))
    assert X.dtype == np.float32 and X.shape == (N, nrhs)
    assert _backward_error(A, X, B) < 50 * EPS32 * N
    # condition about 3.7: the answer itself to a few float32 ulps
    assert np.linalg.norm(X - want) <= 50 * EPS32 * np.linalg.norm(want)
