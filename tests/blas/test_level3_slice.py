"""ISSUE 16: the slicing gemm (``alg='slice'``) -- correctness pins.

Identity vs the stationary-C reference across the full acceptance
matrix {square, tall-skinny, outer-product} x {1x1, 2x2, 2x4} x
{None, bf16, int8}; the degenerate-grid / ragged edge cases the slice
path newly exercises; and the complex-beta bugfix sweep for the
stationary-A/B and gspmd schedules (mirror of the PR 2 ``_summa_dot``
fix)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import elemental_tpu as el
from elemental_tpu import MC, MR, from_global, to_global
from elemental_tpu.blas import level3 as l3
from elemental_tpu.redist.engine import redist_counts


def _rng(seed):
    return np.random.default_rng(seed)


def _dist(g, arr):
    return from_global(jnp.asarray(arr), MC, MR, grid=g)


@pytest.fixture(params=[(1, 1), (2, 2), (2, 4)],
                ids=["1x1", "2x2", "2x4"])
def slice_grid(request):
    r, c = request.param
    return el.Grid(jax.devices()[: r * c], height=r)


#: the acceptance shape classes: square, tall-skinny (m >> n),
#: outer-product (k small)
SHAPES = {"square": (48, 48, 48),
          "tall_skinny": (256, 32, 8),
          "outer_product": (40, 4, 48)}


@pytest.mark.parametrize("shape", sorted(SHAPES), ids=sorted(SHAPES))
def test_slice_identical_to_stationary_c(slice_grid, shape):
    """Full precision (f64): slice agrees with the alg='C' reference to
    roundoff across every shape class x grid of the acceptance matrix."""
    rng = _rng(7)
    m, k, n = SHAPES[shape]
    A, B = rng.normal(size=(m, k)), rng.normal(size=(k, n))
    C0 = rng.normal(size=(m, n))
    args = dict(alpha=1.25, beta=-0.5)
    ref = l3.gemm(_dist(slice_grid, A), _dist(slice_grid, B),
                  C=_dist(slice_grid, C0), alg="C", nb=16, **args)
    got = l3.gemm(_dist(slice_grid, A), _dist(slice_grid, B),
                  C=_dist(slice_grid, C0), alg="slice", **args)
    np.testing.assert_allclose(np.asarray(to_global(got)),
                               np.asarray(to_global(ref)), rtol=1e-12)
    np.testing.assert_allclose(np.asarray(to_global(got)),
                               1.25 * A @ B - 0.5 * C0, rtol=1e-11)


@pytest.mark.parametrize("cp", ["bf16", "int8"])
@pytest.mark.parametrize("shape", sorted(SHAPES), ids=sorted(SHAPES))
def test_slice_comm_precision_residual_class(slice_grid, shape, cp):
    """Quantized wires (bf16 cast / int8 block-scale-pack compose per
    plan slot on the slice gathers): the result stays in the quantized
    residual class of the family (the 5e-2 relative-Frobenius bound the
    other drivers pin)."""
    rng = _rng(11)
    m, k, n = SHAPES[shape]
    A = rng.normal(size=(m, k)).astype(np.float32)
    B = rng.normal(size=(k, n)).astype(np.float32)
    got = np.asarray(to_global(
        l3.gemm(_dist(slice_grid, A), _dist(slice_grid, B), alg="slice",
                comm_precision=cp)), dtype=np.float64)
    ref = A.astype(np.float64) @ B.astype(np.float64)
    assert np.linalg.norm(got - ref) / np.linalg.norm(ref) <= 5e-2
    # 1x1 grids: the knob is a no-op and the early-out is bit-identical
    if slice_grid.size == 1:
        exact = np.asarray(to_global(
            l3.gemm(_dist(slice_grid, A), _dist(slice_grid, B),
                    alg="slice")))
        assert np.array_equal(got.astype(np.float32), exact)


def test_slice_1x1_zero_redistributes():
    """1x1 degeneracy (pinned): slice is ONE local matmul -- zero
    redistribute calls, byte-identical to the dot early-out."""
    g = el.Grid(jax.devices()[:1], height=1)
    rng = _rng(3)
    A, B = rng.normal(size=(33, 17)), rng.normal(size=(17, 21))
    with redist_counts() as counter:
        got = l3.gemm(_dist(g, A), _dist(g, B), alg="slice")
    assert not counter
    dot = l3.gemm(_dist(g, A), _dist(g, B), alg="dot")
    assert np.array_equal(np.asarray(to_global(got)),
                          np.asarray(to_global(dot)))


def test_auto_1x1_keeps_dot_early_out_byte_identical():
    """alg='auto' on 1x1 still resolves to 'dot' and its p==1 early-out:
    zero redistributes, bitwise-equal output (the acceptance pin that
    'slice' joining the space does not perturb the degenerate grid)."""
    g = el.Grid(jax.devices()[:1], height=1)
    rng = _rng(5)
    A, B = rng.normal(size=(64, 32)), rng.normal(size=(32, 48))
    with redist_counts() as counter:
        got = l3.gemm(_dist(g, A), _dist(g, B), alg="auto")
    assert not counter
    dot = l3.gemm(_dist(g, A), _dist(g, B), alg="dot")
    assert np.array_equal(np.asarray(to_global(got)),
                          np.asarray(to_global(dot)))


@pytest.mark.parametrize("r,c", [(4, 1), (1, 8), (8, 1), (1, 4)])
def test_slice_degenerate_1d_grids(r, c):
    """Nx1 / 1xN grids: the mode rule makes two of the three legs local
    relabelings; the answer stays exact (incl. ragged extents)."""
    g = el.Grid(jax.devices()[: r * c], height=r)
    rng = _rng(13)
    for m, k, n in [(64, 16, 48), (23, 9, 31)]:
        A, B = rng.normal(size=(m, k)), rng.normal(size=(k, n))
        got = l3.gemm(_dist(g, A), _dist(g, B), alg="slice")
        np.testing.assert_allclose(np.asarray(to_global(got)), A @ B,
                                   rtol=1e-11)


def test_slice_empty_slot_devices():
    """Ragged FFD edge case: extents SMALLER than the 1-D cyclic order
    leave whole devices with zero owned rows of the [VC,STAR] slice
    (their a2a slots are pure sentinel padding) -- the plan must still
    execute exactly."""
    g = el.Grid(jax.devices()[:4], height=2)
    rng = _rng(17)
    for m in (3, 5, 2):                    # m < p or barely above
        A, B = rng.normal(size=(m, 7)), rng.normal(size=(7, 2))
        got = l3.gemm(_dist(g, A), _dist(g, B), alg="slice")
        np.testing.assert_allclose(np.asarray(to_global(got)), A @ B,
                                   rtol=1e-11)


def test_slice_ignores_nb():
    """'slice' is a one-shot schedule: nb is dead (any value, same
    plan, same bits)."""
    g = el.Grid(jax.devices()[:4], height=2)
    rng = _rng(19)
    A, B = rng.normal(size=(96, 24)), rng.normal(size=(24, 8))
    a = l3.gemm(_dist(g, A), _dist(g, B), alg="slice", nb=8)
    b = l3.gemm(_dist(g, A), _dist(g, B), alg="slice", nb=None)
    assert np.array_equal(np.asarray(to_global(a)),
                          np.asarray(to_global(b)))


# ---------------------------------------------------------------------
# ISSUE 51: the three hops move blocks through the engine's fused
# single-collective kernels, not entries through the plan executor's
# index tables -- the same bits, no gather and no scatter, and a counter
# ---------------------------------------------------------------------

def _through_the_plan_executor(monkeypatch):
    """The route of every commit before PR 51: the slicing gemm's hops
    asked :func:`redistribute` for ``path='direct'``."""
    real = l3.redistribute
    monkeypatch.setattr(
        l3, "redistribute",
        lambda A, cdist, rdist, **kw: real(A, cdist, rdist, path="direct",
                                           **kw))


#: row mode (m >= n), column mode, ragged extents each way, a complex
#: product, an accumulating C, a narrow wire: (m, k, n), dtype, keywords
ROUTE_CASES = {
    "row_mode": ((96, 40, 24), np.float32, {}),
    "column_mode": ((24, 40, 96), np.float32, {}),
    "ragged_rows": ((37, 23, 11), np.float32, {}),
    "ragged_columns": ((11, 23, 37), np.float64, {}),
    "complex": ((45, 17, 29), np.complex64, {}),
    "beta": ((50, 21, 70), np.float32, {"alpha": 1.25, "beta": -0.5}),
    "bf16_wire": ((64, 32, 48), np.float32, {"comm_precision": "bf16"}),
}


@pytest.mark.parametrize("case", sorted(ROUTE_CASES))
@pytest.mark.parametrize("r,c", [(2, 2), (2, 4)], ids=["2x2", "2x4"])
def test_fused_hops_give_the_plan_executors_bits(r, c, case, monkeypatch):
    """Pure data movement either way: the result through the engine's
    fused kernels is the plan executor's, bit for bit."""
    g = el.Grid(jax.devices()[: r * c], height=r)
    (m, k, n), dtype, kw = ROUTE_CASES[case]
    rng = _rng(51)

    def draw(*shape):
        x = rng.normal(size=shape)
        if np.issubdtype(dtype, np.complexfloating):
            x = x + 1j * rng.normal(size=shape)
        return x.astype(dtype)
    A, B, C0 = draw(m, k), draw(k, n), draw(m, n)

    def product():
        C = _dist(g, C0) if "beta" in kw else None
        return np.asarray(to_global(l3.gemm(
            _dist(g, A), _dist(g, B), C=C, alg="slice",
            precision=jax.lax.Precision.HIGHEST, **kw)))
    fused = product()
    _through_the_plan_executor(monkeypatch)
    assert np.array_equal(fused, product())
    want = kw.get("alpha", 1.0) * A.astype(np.complex128) @ B \
        + kw.get("beta", 0.0) * C0
    tol = 2e-2 if "comm_precision" in kw else 1e-5
    assert np.linalg.norm(fused - want) <= tol * np.linalg.norm(want)


@pytest.mark.parametrize("route", ["fused", "plan_executor"])
@pytest.mark.parametrize("shape", [(256, 64, 32), (32, 64, 256)],
                         ids=["row_mode", "column_mode"])
@pytest.mark.parametrize("r,c", [(2, 2), (2, 4)], ids=["2x2", "2x4"])
def test_compiled_slice_gemm_gathers_and_scatters_no_block(r, c, shape,
                                                           route,
                                                           monkeypatch):
    """The compiled program of ONE ``gemm(alg='slice')``: through the
    fused kernels no ``gather`` and no ``scatter`` instruction moves a
    device's share of the smallest operand or more (here: none at all);
    through the plan executor every hop packs with a gather and two of
    them unpack with a scatter, an entry at a time (15 ns each on a
    v5e: PERF.md 6, PR 51) -- which also shows the reader reads."""
    from ..lapack.test_herm_eig_compiled import big_moves
    g = el.Grid(jax.devices()[: r * c], height=r)
    m, k, n = shape
    if route == "plan_executor":
        _through_the_plan_executor(monkeypatch)
    A = _dist(g, np.zeros((m, k), np.float32))
    B = _dist(g, np.zeros((k, n), np.float32))
    text = jax.jit(lambda a, b: l3.gemm(a, b, alg="slice").local).lower(
        A, B).compile().as_text()
    share = min(m * k, k * n, m * n) // (r * c)
    moves = big_moves(text, share)
    if route == "fused":
        assert not moves
        assert not big_moves(text, 1)
    else:
        assert {opcode for _n, opcode, _name in moves} == {"gather",
                                                           "scatter"}
        assert all("_redistribute_direct_jit" in name
                   for _n, _opcode, name in moves)


def test_gemm_route_reads_the_resolved_alg(grid24):
    """``gemm_route{alg}``: one tick a ``gemm`` on a grid of more than one
    device, with what ``'auto'`` RESOLVED to; nothing on 1x1, where every
    schedule is the one local matmul."""
    from elemental_tpu import obs
    from elemental_tpu import tune

    def auto_pick(gshape, g):
        return tune.resolve_knobs(
            "gemm", gshape=gshape, dtype=jnp.float32, grid=g,
            knobs={"alg": "auto", "nb": None, "comm_precision": None,
                   "redist_path": None})["alg"]
    tall, square = (1024, 128, 64), (256, 256, 256)
    assert auto_pick(tall, grid24) == "slice"
    assert auto_pick(square, grid24) == "gspmd"

    def run(g, shape, **kw):
        m, k, n = shape
        return l3.gemm(_dist(g, np.ones((m, k), np.float32)),
                       _dist(g, np.ones((k, n), np.float32)), **kw)
    with obs.metrics_scope() as reg:
        run(grid24, tall)
        run(grid24, tall, alg="auto")
        run(grid24, square)
        run(grid24, square, alg="C", nb=16)
        run(grid24, square, alg="slice")
        run(el.Grid(jax.devices()[:1], height=1), tall)
        run(el.Grid(jax.devices()[:1], height=1), tall, alg="slice")
    assert {labels: v for (_name, labels), v
            in reg.counters("gemm_route").items()} == {
        (("alg", "slice"),): 3, (("alg", "gspmd"),): 1, (("alg", "C"),): 1}


# ---------------------------------------------------------------------
# bugfix sweep: beta accumulation on the stationary-A/B + gspmd paths
# (mirror of the PR 2 _summa_dot complex-beta fix)
# ---------------------------------------------------------------------

@pytest.mark.parametrize("alg", ["A", "B", "slice", "gspmd"])
def test_gemm_complex_beta_real_c_raises(grid24, alg):
    """A complex beta cannot silently land in a REAL C: _safe_astype
    must raise (the stationary-A/B seeds used to skip the check and
    return a complex-typed result)."""
    rng = _rng(23)
    m, k, n = 24, 16, 20
    A, B = rng.normal(size=(m, k)), rng.normal(size=(k, n))
    C0 = rng.normal(size=(m, n))
    with pytest.raises(TypeError):
        l3.gemm(_dist(grid24, A), _dist(grid24, B), beta=0.5j,
                C=_dist(grid24, C0), alg=alg, nb=8)


@pytest.mark.parametrize("alg", ["A", "B", "slice", "gspmd"])
def test_gemm_complex_zero_beta_real_c(grid24, alg):
    """beta=0j on a REAL C behaves as beta=0 on every schedule (the
    gspmd branch used to raise spuriously; A/B used to go complex)."""
    rng = _rng(29)
    m, k, n = 24, 16, 20
    A, B = rng.normal(size=(m, k)), rng.normal(size=(k, n))
    C0 = rng.normal(size=(m, n))
    out = l3.gemm(_dist(grid24, A), _dist(grid24, B), beta=0j,
                  C=_dist(grid24, C0), alg=alg, nb=8)
    assert np.asarray(to_global(out)).dtype.kind == "f"
    np.testing.assert_allclose(np.asarray(to_global(out)), A @ B,
                               rtol=1e-12)


@pytest.mark.parametrize("alg", ["A", "B", "slice", "gspmd"])
def test_gemm_complex_c_real_operands_complex_beta(grid24, alg):
    """Complex C with REAL A, B and complex alpha/beta accumulates
    exactly on every schedule (the previously untested A/B cases)."""
    rng = _rng(31)
    m, k, n = 24, 16, 20
    A, B = rng.normal(size=(m, k)), rng.normal(size=(k, n))
    C0 = rng.normal(size=(m, n)) + 1j * rng.normal(size=(m, n))
    alpha, beta = 1.5 - 0.5j, 0.7 - 0.3j
    out = l3.gemm(_dist(grid24, A), _dist(grid24, B), alpha=alpha,
                  beta=beta, C=_dist(grid24, C0), alg=alg, nb=8)
    np.testing.assert_allclose(np.asarray(to_global(out)),
                               alpha * A @ B + beta * C0, rtol=1e-12)
