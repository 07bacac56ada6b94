"""The singular value decomposition as ONE compiled program (ISSUE 53).

``jax.jit(el.svd)`` on one device and on the 2x2 virtual mesh against
float64 ``numpy.linalg.svd`` of the same matrix: square operands through
the QDWH polar route, a tall one through ``'chan'`` (whose inner SVD of R
is the polar route again), ONE compile request a case and no host
transfer inside the call; the zero matrix and a rank-deficient matrix
through the traced degenerate branch of ``polar``; the scopes and
trace-time counters of the polar stage (grammar in
``elemental_tpu/obs/__init__.py``); and the blocks the composed driver
picks for its stages when it is handed none.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import elemental_tpu as el
from elemental_tpu import obs
from elemental_tpu.lapack.funcs import _polar_blocks, _qdwh_schedule
from elemental_tpu.tune.policy import stage_blocksize
from ..obs.test_scopes import op_names

EPS = float(np.finfo(np.float32).eps)
GRIDS = ["1x1", "2x2"]
#: name -> (rows, cols, nb, approach)
SHAPES = {"square96": (96, 96, None, "auto"),
          "square128": (128, 128, 32, "auto"),
          "tall208x136": (208, 136, 34, "chan")}


def _grid(name):
    r, c = (int(d) for d in name.split("x"))
    return el.Grid(list(jax.devices()[:r * c]), height=r)


def _matrix(m, n, dtype=np.float32):
    return np.random.default_rng(53 + m + n).uniform(
        -1, 1, size=(m, n)).astype(dtype)


def _run(F, grid, **options):
    """``(U, s, V)`` as float64 numpy from ONE compiled call with no host
    transfer inside it, and the registry the trace ticked."""
    A = el.from_global(F, el.MC, el.MR, grid=grid)
    with obs.metrics_scope() as reg:
        fn = jax.jit(lambda a: el.svd(a, **options))
        with jax.transfer_guard("disallow"):
            U, s, V = jax.block_until_ready(fn(A))
    assert reg.counter_value("compile_requests") == 1
    return (np.asarray(el.to_global(U), np.float64), np.asarray(s, np.float64),
            np.asarray(el.to_global(V), np.float64), reg)


def _check(F, U, s, V, tol):
    """Singular values to ``tol`` ||A||_2, ``A V = U S`` to ``tol``
    ||A||_F, both factors orthonormal to ``tol`` sqrt(n)."""
    F = F.astype(np.float64)
    n = F.shape[1]
    want = np.linalg.svd(F, compute_uv=False)
    assert s.shape == (n,) and np.all(s >= 0) and np.all(np.diff(s) <= 0)
    assert np.abs(s - want).max() <= tol * want[0]
    assert np.linalg.norm(F @ V - U * s) <= tol * np.linalg.norm(F)
    for Q in (U, V):
        assert np.linalg.norm(Q.T @ Q - np.eye(n)) <= tol * np.sqrt(n)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("grid_name", GRIDS)
def test_jitted_svd_matches_float64_numpy(grid_name, shape):
    m, n, nb, approach = SHAPES[shape]
    F = _matrix(m, n)
    U, s, V, reg = _run(F, _grid(grid_name), nb=nb, approach=approach)
    assert U.shape == (m, n) and V.shape == (n, n)
    _check(F, U, s, V, tol=100 * EPS)
    # the RESOLVED route: the square operand's and, under 'chan', the
    # inner SVD of R (n > 128) take the polar one
    assert reg.counter_value("svd_route", approach="polar") == 1
    assert reg.counter_value("svd_route", approach="chan") == (
        approach == "chan")
    assert reg.counter_value("qdwh_step", kind="qr") == 2
    assert reg.counter_value("qdwh_step", kind="chol") == 4


def test_float64_takes_its_own_schedule():
    """float64's eps gives 2 QR-based and 6 Cholesky-based steps, and the
    answer float64's accuracy."""
    kinds = ["qr" if c > 100 else "chol"
             for _a, _b, c in _qdwh_schedule(2.0 ** -52, 10 * 2.0 ** -52)]
    assert (kinds.count("qr"), kinds.count("chol")) == (2, 6)
    F = _matrix(64, 64, np.float64)
    U, s, V, reg = _run(F, _grid("1x1"), nb=16)
    _check(F, U, s, V, tol=1e-13)
    assert reg.counter_value("qdwh_step", kind="qr") == 2
    assert reg.counter_value("qdwh_step", kind="chol") == 6
    assert {dict(labels)["stage"]: dict(labels)["nb"] for (_n, labels)
            in reg.counters("polar_block")} == {
                "qr": "16", "chol": "16", "eig": "16"}


def test_values_only_is_one_program_too():
    F = _matrix(64, 64)
    A = el.from_global(F, el.MC, el.MR, grid=_grid("1x1"))
    fn = jax.jit(lambda a: el.svd(a, vectors=False, nb=16))
    with jax.transfer_guard("disallow"):
        s = jax.block_until_ready(fn(A))
    want = np.linalg.svd(F.astype(np.float64), compute_uv=False)
    assert np.abs(np.asarray(s, np.float64) - want).max() <= 100 * EPS * want[0]


def test_zero_matrix_takes_the_traced_degenerate_branch():
    """``polar`` of the zero matrix is ``(I, 0)`` by a select on the
    device; its SVD is s = 0 with orthonormal U and V."""
    n = 64
    F = np.zeros((n, n), np.float32)
    A = el.from_global(F, el.MC, el.MR, grid=_grid("1x1"))
    Up, H = jax.jit(lambda a: el.polar(a, nb=16))(A)
    assert np.array_equal(np.asarray(el.to_global(Up)), np.eye(n))
    assert not np.asarray(el.to_global(H)).any()
    U, s, V, _reg = _run(F, _grid("1x1"), nb=16)
    assert not s.any()
    for Q in (U, V):
        assert np.linalg.norm(Q.T @ Q - np.eye(n)) <= 100 * EPS * np.sqrt(n)


def test_non_finite_operand_takes_it_too():
    """As the host branch did: a non-finite scale gives ``(I, 0)``."""
    F = np.ones((32, 32), np.float32)
    F[3, 4] = np.inf
    A = el.from_global(F, el.MC, el.MR, grid=_grid("1x1"))
    Up, H = jax.jit(lambda a: el.polar(a, nb=32))(A)
    assert np.array_equal(np.asarray(el.to_global(Up)), np.eye(32))
    assert not np.asarray(el.to_global(H)).any()


def test_rank_deficient_matrix():
    """Rank 40 of 64: the singular values (24 of them at rounding), V
    orthonormal and ``A V = U S`` hold; U is orthonormal on the columns of
    the non-zero singular values (a null direction of A has no image to
    normalize: QDWH's U_p is a partial isometry there)."""
    n, rank = 64, 40
    rng = np.random.default_rng(7)
    F = (rng.uniform(-1, 1, size=(n, rank))
         @ rng.uniform(-1, 1, size=(rank, n))).astype(np.float32)
    U, s, V, _reg = _run(F, _grid("1x1"), nb=16)
    F64 = F.astype(np.float64)
    want = np.linalg.svd(F64, compute_uv=False)
    tol = 100 * EPS
    assert np.all(s >= 0) and np.all(np.diff(s) <= 0)
    assert np.abs(s - want).max() <= tol * want[0]
    assert s[rank:].max() <= tol * want[0] < s[rank - 1]
    assert np.linalg.norm(F64 @ V - U * s) <= tol * np.linalg.norm(F64)
    assert np.linalg.norm(V.T @ V - np.eye(n)) <= tol * np.sqrt(n)
    Ur = U[:, :rank]
    assert np.linalg.norm(Ur.T @ Ur - np.eye(rank)) <= tol * np.sqrt(n)


def test_eager_calls_give_what_the_compiled_ones_give():
    """The eager call is the same code, untraced."""
    F = _matrix(32, 32)
    g = _grid("1x1")
    A = el.from_global(F, el.MC, el.MR, grid=g)
    U, s, V = el.svd(A, nb=16)
    Uc, sc, Vc = jax.jit(lambda a: el.svd(a, nb=16))(A)
    assert np.allclose(np.asarray(s), np.asarray(sc), atol=10 * EPS * 32)
    _check(F, np.asarray(el.to_global(U), np.float64),
           np.asarray(s, np.float64),
           np.asarray(el.to_global(V), np.float64), tol=100 * EPS)
    with pytest.raises(ValueError, match="unknown svd approach"):
        el.svd(A, approach="jacobi")


# ------------------------------------------------- scopes and counters

@pytest.fixture(scope="module")
def program_text():
    """Optimized HLO of the jitted square SVD at n = 64, nb 16."""
    A = el.from_global(_matrix(64, 64), el.MC, el.MR, grid=_grid("1x1"))
    return jax.jit(lambda a: el.svd(a, nb=16)).lower(A).compile().as_text()


def test_the_stages_carry_their_scopes(program_text):
    """Everything under ``el.svd``; the six steps under two named
    segments that say variant and numbers and are no ``k<step>``; the
    nested drivers' ops keep their own phase after them; ``polar_h``, ``svd_u``
    and the inner ``el.herm_eig`` as the grammar says."""
    names = [n for n in op_names(program_text) if "el.svd" in n.split("/")]
    assert names

    def under(*segments):
        chain = "/(?:.*/)?".join(re.escape(s) for s in segments)
        return [n for n in names if re.search(chain, n)]
    steps = sorted({seg for n in names for seg in n.split("/")
                    if seg.startswith("qdwh_")})
    assert steps == ["qdwh_chol03_06", "qdwh_qr01_02"]
    assert not any(re.fullmatch(r"k\d{2,}", s) for s in steps)
    # each variant's steps are ONE loop body, the segment opened inside it
    assert under("el.polar", "while", "body", "qdwh_qr01_02", "el.qr",
                 "k00", "panel")
    assert under("el.polar", "qdwh_qr01_02", "el.qr", "k01", "update")
    assert under("el.polar", "qdwh_qr01_02", "el.gemm")
    # the thin Q's sweep under a name of its own, beside the factorization
    assert under("el.polar", "while", "body", "qdwh_qr01_02", "el.thin_q",
                 "k00", "apply")
    assert under("el.polar", "qdwh_qr01_02", "el.thin_q", "k03", "apply")
    assert not under("el.qr", "el.thin_q") and not under("el.thin_q", "el.qr")
    assert under("el.polar", "while", "body", "qdwh_chol03_06", "el.herk")
    assert under("el.polar", "qdwh_chol03_06", "el.cholesky")
    assert under("el.polar", "qdwh_chol03_06", "el.trsm")
    assert under("el.polar", "polar_h", "el.gemm")
    assert under("svd_u", "el.gemm")
    assert under("el.herm_eig", "el.hermitian_tridiag")
    assert under("el.herm_eig", "el.apply_q_herm_tridiag")
    # nothing of the eigensolve or of U = U_p V under el.polar
    assert not under("el.polar", "el.herm_eig")
    assert not under("el.polar", "svd_u")
    assert "custom_call_target=\"xla_python" not in program_text


def test_the_qr_based_step_runs_over_its_stacks_structure(program_text):
    """ISSUE 54, n = 64, nb 16: no (2n x n) identity is made (the thin Q
    starts from zeros and builds a panel's own columns of ``[I; 0]``
    directly, so the program holds no mask of that shape), and every
    product of the factorization and of the thin Q has the panel's nb rows
    or the cut block's n + nb, never the stack's ``2n - s``."""
    n, nb = 64, 16
    assert not re.search(rf"pred\[{2 * n},{n}\]", program_text)
    rows = set()
    for line in program_text.splitlines():
        found = re.search(r"= f32\[(\d+),\d+\]\S* dot\(", line)
        if found and re.search(
                r'op_name="[^"]*/qdwh_qr01_02/el\.(qr|thin_q)/', line):
            rows.add(int(found.group(1)))
    assert rows == {nb, n + nb}


# ---------------------------------------------------- the picked blocks

class _Grid:
    def __init__(self, height, width):
        self.height, self.width = height, width


@pytest.mark.parametrize("n, want", [
    (16384, {"qr": 512, "chol": 2048, "eig": 256}),     # the cell's order
    (32768, {"qr": 512, "chol": 2048, "eig": 256}),
    (8192, {"qr": 512, "chol": 1024, "eig": 256}),
    (1024, {"qr": 128, "chol": 128, "eig": 128}),
    (96, {"qr": 128, "chol": 128, "eig": 128}),         # clamped by stage
])
def test_blocks_picked_with_no_nb(n, want):
    """What the cells measured on one chip at size: 256 for the
    eigensolve's reduction, 2048 for the stages whose panel is blocked;
    512 for ``qr`` / ``apply_q``, whose panels the program unrolls twice;
    an explicit ``nb`` goes to every stage."""
    assert _polar_blocks(None, n, n, _Grid(1, 1), jnp.float32) == want
    assert _polar_blocks(None, n, n, _Grid(2, 2), jnp.float32) == want
    assert _polar_blocks(64, n, n, _Grid(1, 1), jnp.float32) == {
        "qr": 64, "chol": 64, "eig": 64}


def test_stage_blocksize_reads_dtype_and_grid():
    g1, g3 = _Grid(1, 1), _Grid(3, 2)
    # the same bytes a panel: half the columns for 8-byte entries
    assert stage_blocksize("block", 16384, g1, jnp.float64) == 1024
    assert stage_blocksize("block", 16384, g1, jnp.complex64) == 1024
    assert stage_blocksize("reduce", 16384, g1, jnp.float64) == 128
    assert stage_blocksize("block", 16384, g1, jnp.bfloat16) == 2048
    assert stage_blocksize("qr", 16384, g1, jnp.float32) == 512
    # rounded up to the grid's grain (lcm 6)
    assert stage_blocksize("reduce", 16384, g3, jnp.float32) == 258
    assert stage_blocksize("block", 16384, g3, jnp.float32) % 6 == 0
    with pytest.raises(KeyError):
        stage_blocksize("panel", 16384, g1, jnp.float32)
