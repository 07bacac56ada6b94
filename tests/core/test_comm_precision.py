"""Quantized block-scaled collectives (ISSUE 8): codec round-trip
invariants, engine wire routing, and the zero-overhead ``None`` pin.

Covers the satellite acceptance list verbatim: bf16 exactness on
bf16-representable values, the int8 block-scale error bound against the
documented ``amax_tile / 127`` factor, NaN/Inf payloads passing through
un-masked (so the resilience health guards still see them), and
redist-count equality pinning ``comm_precision=None`` as the
bit-identical zero-overhead path.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import elemental_tpu as el
from elemental_tpu import MC, MR, from_global, to_global
from elemental_tpu.core.dist import STAR, VC
from elemental_tpu.redist import engine
from elemental_tpu.redist.quantize import (COMM_PRECISIONS, QUANT_TILE,
                                           q8_decode, q8_encode, q8_pack,
                                           q8_unpack)

from ..conftest import compiled

RNG = np.random.default_rng(1234)


def _grid(r, c):
    return el.Grid(jax.devices()[: r * c], height=r)


# ---------------------------------------------------------------------
# codec invariants (pure, device-free semantics)
# ---------------------------------------------------------------------

def test_comm_precision_vocabulary_pinned():
    assert COMM_PRECISIONS == (None, "bf16", "int8")
    from elemental_tpu.tune.knobs import COMM_PRECISIONS as TUNE_CP
    assert TUNE_CP == COMM_PRECISIONS


def test_int8_block_scale_error_bound():
    """|x - decode(encode(x))| <= amax_tile / 127 per element -- the
    documented bound (round-to-nearest actually achieves half of it; the
    full factor is what the README promises)."""
    x = RNG.normal(size=(3 * QUANT_TILE + 7, 2 * QUANT_TILE + 5))
    x = (x * np.logspace(0, 3, x.shape[1])[None, :]).astype(np.float32)
    q, scales = q8_encode(jnp.asarray(x))
    back = np.asarray(q8_decode(q, scales, jnp.float32))
    tr, tc = -(-x.shape[0] // QUANT_TILE), -(-x.shape[1] // QUANT_TILE)
    for ti in range(tr):
        for tj in range(tc):
            blk = x[ti * QUANT_TILE:(ti + 1) * QUANT_TILE,
                    tj * QUANT_TILE:(tj + 1) * QUANT_TILE]
            dec = back[ti * QUANT_TILE:(ti + 1) * QUANT_TILE,
                       tj * QUANT_TILE:(tj + 1) * QUANT_TILE]
            bound = np.abs(blk).max() / 127.0 + 1e-12
            assert np.abs(blk - dec).max() <= bound, (ti, tj)


def test_int8_zero_tiles_roundtrip_exactly():
    x = jnp.zeros((QUANT_TILE * 2, QUANT_TILE), jnp.float32)
    q, scales = q8_encode(x)
    assert np.asarray(q8_decode(q, scales, jnp.float32)).max() == 0.0


def test_q8_pack_unpack_is_encode_decode():
    """The bitcast scale-packing transport is lossless: unpack(pack(x))
    equals decode(encode(x)) bit for bit, at ragged shapes too."""
    for shape in ((QUANT_TILE, QUANT_TILE), (70, 33), (5, 129)):
        x = jnp.asarray(RNG.normal(size=shape).astype(np.float32)) * 100
        q, scales = q8_encode(x)
        via_codec = np.asarray(q8_decode(q, scales, jnp.float32))
        via_pack = np.asarray(q8_unpack(q8_pack(x), shape, jnp.float32))
        assert (via_codec == via_pack).all(), shape
        assert q8_pack(x).dtype == jnp.int8


def test_nan_inf_pass_through_unmasked():
    """Non-finite payloads must stay non-finite after decode (tile
    granular): the health guards' NaN/Inf scans keep their teeth under
    quantized wire."""
    x = RNG.normal(size=(2 * QUANT_TILE, 2 * QUANT_TILE)).astype(np.float32)
    x[3, 5] = np.nan
    x[QUANT_TILE + 2, QUANT_TILE + 9] = np.inf
    q, scales = q8_encode(jnp.asarray(x))
    back = np.asarray(q8_decode(q, scales, jnp.float32))
    assert not np.isfinite(back[3, 5])
    assert not np.isfinite(back[QUANT_TILE + 2, QUANT_TILE + 9])
    # clean tiles stay clean (corruption is tile-granular, not global)
    assert np.isfinite(back[:QUANT_TILE, QUANT_TILE:]).all()


def test_bad_mode_raises():
    g = _grid(1, 1)
    A = from_global(np.eye(8, dtype=np.float32), MC, MR, grid=g)
    with pytest.raises(ValueError, match="comm_precision"):
        engine.redistribute(A, STAR, STAR, comm_precision="fp8")
    with pytest.raises(ValueError, match="comm_precision"):
        el.lu(A, nb=4, comm_precision="fp8")


# ---------------------------------------------------------------------
# engine routing
# ---------------------------------------------------------------------

def test_bf16_exact_on_representable_values(grid24):
    """bf16 wire is EXACT for bf16-representable payloads (small ints,
    powers of two): the cast is the only perturbation."""
    vals = RNG.integers(-128, 128, size=(32, 32)).astype(np.float32)
    A = from_global(vals, MC, MR, grid=grid24)
    out = engine.redistribute(A, STAR, STAR, comm_precision="bf16")
    assert out.dtype == A.dtype
    assert (np.asarray(to_global(out)) == vals).all()


@pytest.mark.parametrize("mode", ["bf16", "int8"])
def test_quantized_gather_roundtrip_error_bound(grid24, mode):
    arr = (RNG.normal(size=(48, 40)) * 10).astype(np.float32)
    A = from_global(arr, MC, MR, grid=grid24)
    out = np.asarray(to_global(engine.redistribute(A, STAR, STAR,
                                                   comm_precision=mode)))
    bound = np.abs(arr).max() * (1 / 127.0 if mode == "int8" else 1 / 128.0)
    assert np.abs(out - arr).max() <= bound + 1e-12
    # wire dtype is recorded on the trace record
    with engine.redist_trace() as log:
        engine.redistribute(A, STAR, STAR, comm_precision=mode)
    assert log[-1].wire_dtype == {"bf16": "bfloat16", "int8": "int8"}[mode]
    assert log[-1].dtype == "float32"


def test_panel_spread_quantized(grid24):
    arr = (RNG.normal(size=(64, 8)) * 3).astype(np.float32)
    P = from_global(arr, VC, STAR, grid=grid24)
    mc0, mr0 = engine.panel_spread(P)
    for mode in ("bf16", "int8"):
        mc, mr = engine.panel_spread(P, comm_precision=mode)
        bound = np.abs(arr).max() / (127.0 if mode == "int8" else 128.0)
        assert np.abs(np.asarray(to_global(mc))
                      - np.asarray(to_global(mc0))).max() <= bound + 1e-12
        assert np.abs(np.asarray(to_global(mr))
                      - np.asarray(to_global(mr0))).max() <= bound + 1e-12


def test_wire_mode_noops(grid24):
    """The knob is a no-op (bit-identical) where it cannot save a byte:
    1x1 grids, replicated sources, non-real-float payloads."""
    arr = RNG.normal(size=(16, 16)).astype(np.float32)
    # 1x1 grid: collectives elide, so quantization would only cost bits
    g1 = _grid(1, 1)
    A1 = from_global(arr, MC, MR, grid=g1)
    out = engine.redistribute(A1, STAR, STAR, comm_precision="int8")
    assert (np.asarray(to_global(out)) == arr).all()
    # replicated source: every target is a pure-local filter
    ss = from_global(arr, STAR, STAR, grid=grid24)
    out = engine.redistribute(ss, MC, MR, comm_precision="int8")
    assert (np.asarray(to_global(out)) == arr).all()
    # complex payload: the codec does not apply
    carr = (arr + 1j * arr).astype(np.complex64)
    Ac = from_global(carr, MC, MR, grid=grid24)
    outc = engine.redistribute(Ac, STAR, STAR, comm_precision="bf16")
    assert (np.asarray(to_global(outc)) == carr).all()


def test_int8_falls_back_to_bf16_off_the_gather_family(grid24):
    """Pairs without a fused int8 kernel degrade to the accuracy-safer
    bf16 cast -- recorded as bfloat16 wire, never silently full fat."""
    arr = RNG.normal(size=(32, 32)).astype(np.float32)
    A = from_global(arr, MC, MR, grid=grid24)
    with engine.redist_trace() as log:
        engine.redistribute(A, VC, STAR, comm_precision="int8")
    assert log[-1].wire_dtype == "bfloat16"


# ---------------------------------------------------------------------
# comm_precision=None: the bit-identical zero-overhead path
# ---------------------------------------------------------------------

def test_none_is_bit_identical_and_count_equal(grid24, redist_counter):
    """lu/cholesky with comm_precision=None produce bit-identical results
    through the SAME redistribution schedule (count equality) as the
    knob-free call -- None costs nothing, pinned."""
    n, nb = 32, 8
    F = RNG.normal(size=(n, n)).astype(np.float32)
    spd = (F @ F.T / n + n * np.eye(n)).astype(np.float32)
    A = from_global(F + n * np.eye(n, dtype=np.float32), MC, MR, grid=grid24)
    S = from_global(spd, MC, MR, grid=grid24)

    with engine.redist_counts() as c0:
        LU0, p0 = compiled(el.lu, nb=nb)(A)
        L0 = compiled(el.cholesky, nb=nb)(S)
    with engine.redist_counts() as c1:
        LU1, p1 = compiled(el.lu, nb=nb, comm_precision=None)(A)
        L1 = compiled(el.cholesky, nb=nb, comm_precision=None)(S)
    assert dict(c0) == dict(c1)
    assert (np.asarray(LU0.local) == np.asarray(LU1.local)).all()
    assert (np.asarray(p0) == np.asarray(p1)).all()
    assert (np.asarray(L0.local) == np.asarray(L1.local)).all()


# ---------------------------------------------------------------------
# end-to-end quantized drivers: documented residual class
# ---------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["bf16", "int8"])
def test_lu_quantized_residual_class(grid24, mode):
    n, nb = 48, 8
    m = (RNG.normal(size=(n, n)) + n * np.eye(n)).astype(np.float32)
    A = from_global(m, MC, MR, grid=grid24)
    LU, perm = compiled(el.lu, nb=nb, comm_precision=mode)(A)
    lu_g = np.asarray(to_global(LU), dtype=np.float64)
    L = np.tril(lu_g, -1) + np.eye(n)
    U = np.triu(lu_g)
    pa = m.astype(np.float64)[np.asarray(perm)]
    resid = np.linalg.norm(pa - L @ U) / np.linalg.norm(m)
    assert resid <= 5e-2, resid          # documented ~1e-2..1e-3 class
    assert np.isfinite(lu_g).all()


@pytest.mark.parametrize("mode", ["bf16", "int8"])
def test_cholesky_quantized_residual_class(grid24, mode):
    n, nb = 48, 8
    F = RNG.normal(size=(n, n))
    spd = (F @ F.T / n + n * np.eye(n)).astype(np.float32)
    S = from_global(spd, MC, MR, grid=grid24)
    L = np.asarray(to_global(compiled(el.cholesky, nb=nb,
                                      comm_precision=mode)(S)),
                   dtype=np.float64)
    resid = np.linalg.norm(spd - L @ L.T) / np.linalg.norm(spd)
    assert resid <= 5e-2, resid
    assert np.isfinite(L).all()


def test_qr_trsm_herk_gemm_accept_the_knob(grid24):
    """Every driver in the tuner's registry accepts comm_precision and
    stays within the quantized residual class."""
    n, nb = 32, 8
    m = RNG.normal(size=(n, n)).astype(np.float32)
    A = from_global(m, MC, MR, grid=grid24)
    B = from_global(RNG.normal(size=(n, n)).astype(np.float32), MC, MR,
                    grid=grid24)
    packed, tau = compiled(el.qr, nb=nb, comm_precision="bf16")(A)
    R = np.triu(np.asarray(to_global(packed), dtype=np.float64))[:n]
    # |R| diag magnitudes match numpy's to the quantized class
    Rn = np.linalg.qr(m.astype(np.float64))[1]
    assert np.abs(np.abs(np.diag(R)) - np.abs(np.diag(Rn))).max() \
        <= 5e-2 * np.abs(np.diag(Rn)).max()
    T = from_global(np.tril(m) + n * np.eye(n, dtype=np.float32), MC, MR,
                    grid=grid24)
    X = el.trsm("L", "L", "N", T, B, nb=nb, comm_precision="bf16")
    tn = np.tril(m).astype(np.float64) + n * np.eye(n)
    assert np.linalg.norm(tn @ np.asarray(to_global(X), dtype=np.float64)
                          - np.asarray(to_global(B))) \
        / np.linalg.norm(np.asarray(to_global(B))) <= 5e-2
    H = el.herk("L", A, nb=nb, comm_precision="bf16")
    ref = np.tril(m.astype(np.float64) @ m.astype(np.float64).T)
    got = np.tril(np.asarray(to_global(H), dtype=np.float64))
    assert np.linalg.norm(got - ref) / np.linalg.norm(ref) <= 5e-2
    G = el.gemm(A, B, alg="C", nb=nb, comm_precision="bf16")
    refg = m.astype(np.float64) @ np.asarray(to_global(B), dtype=np.float64)
    assert np.linalg.norm(np.asarray(to_global(G), dtype=np.float64) - refg) \
        / np.linalg.norm(refg) <= 5e-2


# ---------------------------------------------------------------------
# obs: wire bytes are measured end-to-end
# ---------------------------------------------------------------------

def test_tracer_reports_wire_vs_logical_bytes(grid24):
    from elemental_tpu.obs import metrics as obs_metrics
    from elemental_tpu.obs.tracer import Tracer
    n, nb = 32, 8
    F = RNG.normal(size=(n, n))
    spd = (F @ F.T / n + n * np.eye(n)).astype(np.float32)
    S = from_global(spd, MC, MR, grid=grid24)
    with obs_metrics.scoped() as reg:
        with Tracer() as tr:
            el.cholesky(S, nb=nb, comm_precision="bf16")
    assert tr.redist_bytes_total() > 0
    assert 0 < tr.redist_wire_bytes_total() < tr.redist_bytes_total()
    # bf16 halves every quantized entry; diagonal-block and panel moves
    # all quantize here, so the total is half (small slack for any
    # entry the engine declined to quantize)
    assert tr.redist_wire_bytes_total() <= 0.75 * tr.redist_bytes_total()
    wire = sum(v for (name, _), v in reg.counters().items()
               if name == "redist_wire_bytes")
    assert wire == tr.redist_wire_bytes_total()
    # unquantized runs: wire == logical
    with Tracer() as tr0:
        el.cholesky(S, nb=nb)
    assert tr0.redist_wire_bytes_total() == tr0.redist_bytes_total()
