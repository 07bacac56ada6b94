"""``lu._tri_matmul``: a panel's product against a block-triangular inverse
over the inverse's non-zero blocks only (ISSUE 47).

Both orientations on inverses that the three builders make themselves
(``lu._upper_inv``, ``lu._unit_lower_inv``, ``cholesky._potrf_inv_impl``),
float32, float64 and complex64, at a width that is a multiple of the block,
a ragged one and one under two blocks (the dense side, which is
``jnp.matmul`` bit for bit): against the dense product at HIGHEST and
against float64 numpy.  What the helper relies on, the builders' exact
zeros past the block diagonal, is asserted at the block width the module
ships.  The flops the drivers' panel products run are counted in their
jaxprs, and the trace-time counter ``panel_tri_product{kind}`` says which
side every product of ``cholesky``, ``lu_nopiv`` and ``lu`` took, on one
chip and on the 2x2 CPU mesh.
"""
import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import elemental_tpu as el
from elemental_tpu import obs

lu_mod = importlib.import_module("elemental_tpu.lapack.lu")
chol_mod = importlib.import_module("elemental_tpu.lapack.cholesky")
mixed = importlib.import_module("elemental_tpu.lapack.mixed")

HI = lax.Precision.HIGHEST
#: the block width the tests patch in, and the builders' own block under it
C = 32
DTYPES = {"f32": np.float32, "f64": np.float64, "c64": np.complex64}
#: widths: four blocks, three blocks and a ragged fourth, under two blocks
WIDTHS = {"multiple": 4 * C, "ragged": 2 * C + 37, "narrow": 2 * C - 1}
BUILDERS = ("upper_inv", "potrf_inv", "unit_lower_inv")


@pytest.fixture
def block(monkeypatch):
    monkeypatch.setattr(lu_mod, "TRI_BLOCK", C)
    return C


def _random(rng, shape, dtype):
    x = rng.standard_normal(shape)
    if np.issubdtype(dtype, np.complexfloating):
        x = x + 1j * rng.standard_normal(shape)
    return x.astype(dtype)


def _inverse(builder, w, dtype, bs, seed=0):
    """``(side, Tri)``: the triangular inverse as its builder hands it to
    the drivers' panel product, built in blocks of ``bs``."""
    rng = np.random.default_rng(seed)
    G = _random(rng, (w, w), dtype)
    if builder == "upper_inv":
        U = np.triu(G) + 2 * np.sqrt(w) * np.eye(w, dtype=dtype)
        return "right", lu_mod._upper_inv(jnp.asarray(U), w, bs=bs)
    if builder == "unit_lower_inv":
        L = np.tril(G, -1) / np.sqrt(w) + np.eye(w, dtype=dtype)
        return "left", lu_mod._unit_lower_inv(jnp.asarray(L), w, bs=bs)
    D = G @ G.conj().T + w * np.eye(w, dtype=dtype)
    _, Li = chol_mod._potrf_inv_impl(jnp.asarray(D), None, bs=bs)
    return "right", jnp.conj(Li).T


def _operands(builder, width, dtype, bs, m=200):
    side, Tri = _inverse(builder, width, dtype, bs)
    other = jnp.asarray(_random(np.random.default_rng(1),
                                (m, width) if side == "right"
                                else (width, m), dtype))
    return (side, other, Tri) if side == "right" else (side, Tri, other)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("width", ["multiple", "ragged"])
@pytest.mark.parametrize("builder", BUILDERS)
def test_blocked_product_is_the_dense_one_but_for_summation_order(
        builder, width, dtype, block):
    dt = DTYPES[dtype]
    side, A, B = _operands(builder, WIDTHS[width], dt, bs=block // 2
                           if builder != "potrf_inv" else block)
    with obs.metrics_scope() as reg:
        got = lu_mod._tri_matmul(A, B, side, HI)
    assert dict(reg.counters("panel_tri_product")) == {
        ("panel_tri_product", (("kind", "blocked"),)): 1}
    dense = jnp.matmul(A, B, precision=HI)
    assert got.shape == dense.shape and got.dtype == dense.dtype
    # a few ulp of what a sum's terms add up to in absolute value
    A64, B64 = (np.asarray(M).astype(np.result_type(dt, np.float64))
                for M in (A, B))
    scale = np.abs(A64) @ np.abs(B64)
    eps = np.finfo(dt).eps
    assert np.all(np.abs(np.asarray(got) - np.asarray(dense)) <= 8 * eps * scale)
    assert np.all(np.abs(np.asarray(got) - A64 @ B64)
                  <= (A.shape[1] + 8) * eps * scale)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("builder", BUILDERS)
def test_a_narrow_inverse_takes_the_one_dense_matmul(builder, dtype, block):
    side, A, B = _operands(builder, WIDTHS["narrow"], DTYPES[dtype],
                           bs=block // 2)
    with obs.metrics_scope() as reg:
        got = lu_mod._tri_matmul(A, B, side, HI)
    assert dict(reg.counters("panel_tri_product")) == {
        ("panel_tri_product", (("kind", "dense"),)): 1}
    assert np.array_equal(np.asarray(got),
                          np.asarray(jnp.matmul(A, B, precision=HI)))


@pytest.mark.parametrize("builder", BUILDERS)
def test_builders_leave_exact_zeros_past_the_block_diagonal(builder):
    """At the width the module ships and the builders' OWN block orders
    (256, 256, 512): what ``_tri_matmul`` never multiplies is exactly
    zero, at a width that is no multiple of anything."""
    c = lu_mod.TRI_BLOCK
    w = 2 * c + 37
    bs = 512 if builder == "potrf_inv" else 256
    assert c % bs == 0
    side, Tri = _inverse(builder, w, np.float32, bs)
    Tri = np.asarray(Tri)
    for s in range(0, w, c):
        e = min(s + c, w)
        skipped = Tri[e:, s:e] if side == "right" else Tri[s:e, e:]
        assert not skipped.any()
    # and the triangle is there
    assert np.abs(np.diag(Tri)).min() > 0


def _dot_flops(jaxpr):
    """Flops of every ``dot_general`` of a jaxpr: a ``scan``'s body (a
    ``fori_loop`` of known trip count) as often as it runs, any other
    sub-jaxpr once."""
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (_, rc), (_, rb) = eqn.params["dimension_numbers"]
            lhs, rhs = (v.aval.shape for v in eqn.invars)
            free = [d for i, d in enumerate(rhs) if i not in (*rc, *rb)]
            total += 2 * math.prod(lhs) * math.prod(free)
        times = eqn.params["length"] if eqn.primitive.name == "scan" else 1
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (list, tuple)) else [val]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    total += times * _dot_flops(sub)
    return total


def _lu_nopiv_flops(n, nb):
    A = el.from_global(np.eye(n, dtype=np.float32), el.MC, el.MR,
                       grid=el.Grid([jax.devices()[0]]))
    return _dot_flops(jax.make_jaxpr(
        lambda a: mixed.lu_nopiv(a, nb=nb))(A).jaxpr)


def _chol_array_flops(n, nb):
    return _dot_flops(jax.make_jaxpr(
        lambda a: chol_mod._local_chol_array(a, n, nb, None))(
            jnp.eye(n, dtype=jnp.float32)).jaxpr)


@pytest.mark.parametrize("driver,products", [
    pytest.param(_lu_nopiv_flops, 2, id="lu_nopiv"),
    pytest.param(_chol_array_flops, 1, id="local_chol_array")])
def test_panel_products_run_the_triangles_flops(driver, products,
                                                monkeypatch):
    """``n = 4 nb``, ``nb = 4 c`` (t = 4), traced only: against the same
    program with the dense rule (the block width patched past ``nb``), the
    panels' flops fall from ``sum_k 2 (n - o_k) nb^2`` a product to
    ``(1 + 1/t) / 2`` of it, and nothing else changes."""
    t = 4
    nb = t * C
    n = 4 * nb
    monkeypatch.setattr(lu_mod, "TRI_BLOCK", nb)       # 2 c > nb: dense
    dense = driver(n, nb)
    monkeypatch.setattr(lu_mod, "TRI_BLOCK", C)
    blocked = driver(n, nb)
    formula = products * sum(2 * (n - o) * nb * nb
                             for o in range(nb, n, nb))
    assert 2 * (dense - blocked) == formula - formula // t
    assert 2 * (formula - (dense - blocked)) == formula + formula // t


GRIDS = {"1x1": (1, 1), "2x2": (2, 2)}


def _trace(driver, grid_name, n, nb):
    r, c = GRIDS[grid_name]
    grid = el.Grid(list(jax.devices()[:r * c]), height=r)
    A = el.from_global(np.eye(n, dtype=np.float32), el.MC, el.MR, grid=grid)
    fn = {"cholesky": lambda a: el.cholesky(a, nb=nb),
          "lu_nopiv": lambda a: mixed.lu_nopiv(a, nb=nb),
          "lu": lambda a: el.lu(a, nb=nb)}[driver]
    with obs.metrics_scope() as reg:
        jax.make_jaxpr(fn)(A)
    return {labels[0][1]: count for (_, labels), count
            in dict(reg.counters("panel_tri_product")).items()}


@pytest.mark.parametrize("kind", ["blocked", "dense"])
@pytest.mark.parametrize("grid_name", GRIDS)
@pytest.mark.parametrize("driver,per_step", [
    ("cholesky", 1), ("lu_nopiv", 2), ("lu", 1)])
def test_every_panel_product_ticks_the_side_it_took(driver, per_step,
                                                    grid_name, kind, block):
    """One tick a panel product, every step but the last (nothing lies
    beside its diagonal block): ``blocked`` from ``nb = 2 c`` on, ``dense``
    under it, whatever the grid."""
    nb = 2 * block if kind == "blocked" else block
    steps = 4
    assert _trace(driver, grid_name, steps * nb, nb) == {
        kind: per_step * (steps - 1)}
