"""THE redistribution conformance matrix.

Port of the semantics of the reference's ``tests/core/DistMatrix.cpp`` (the
single most important test, per SURVEY.md §5): fill A[U,V] with a known
f(i,j), set B[U',V'] = A for every legal pair, and verify every entry.
Swept over all src x dst pairs, several grid shapes, and alignments.
"""
import numpy as np
import pytest

from elemental_tpu import LEGAL_PAIRS, from_global, to_global, redistribute, transpose_dist
from elemental_tpu.redist import engine


def f(m, n):
    i = np.arange(m)[:, None]
    j = np.arange(n)[None, :]
    return (i * 997.0 + j + 1).astype(np.float64)


PAIR_IDS = [f"{p[0].value},{p[1].value}" for p in LEGAL_PAIRS]


@pytest.mark.parametrize("dst", LEGAL_PAIRS, ids=PAIR_IDS)
@pytest.mark.parametrize("src", LEGAL_PAIRS, ids=PAIR_IDS)
def test_conformance_grid24(grid24, src, dst):
    F = f(13, 9)
    A = from_global(F, *src, grid=grid24)
    B = redistribute(A, *dst)
    assert B.dist == dst
    np.testing.assert_array_equal(np.asarray(to_global(B)), F)


@pytest.mark.parametrize("dst", LEGAL_PAIRS, ids=PAIR_IDS)
def test_conformance_from_mcmr_all_grids(any_grid, dst):
    from elemental_tpu import MC, MR

    F = f(17, 5)
    A = from_global(F, MC, MR, grid=any_grid)
    B = redistribute(A, *dst)
    C = redistribute(B, MC, MR)
    np.testing.assert_array_equal(np.asarray(to_global(B)), F)
    np.testing.assert_array_equal(np.asarray(to_global(C)), F)


@pytest.mark.parametrize("calign,ralign", [(1, 1), (0, 3), (1, 2)])
@pytest.mark.parametrize("dst", [p for p in LEGAL_PAIRS if p[0].value in ("MC", "VC", "STAR")][:6],
                         ids=lambda p: f"{p[0].value},{p[1].value}")
def test_conformance_aligned(grid24, dst, calign, ralign):
    """Nonzero alignments exercise the generic engine path."""
    from elemental_tpu import MC, MR

    F = f(11, 7)
    A = from_global(F, MC, MR, grid=grid24, calign=1, ralign=2)
    B = redistribute(A, *dst, calign=calign % 2, ralign=ralign)
    np.testing.assert_array_equal(np.asarray(to_global(B)), F)


def test_transpose_dist(grid24):
    from elemental_tpu import MC, MR
    import jax

    F = f(12, 8)
    A = from_global(F, MC, MR, grid=grid24)

    def tfn(a):
        return transpose_dist(a)

    out_meta = transpose_dist(A)  # storage-level transpose has same semantics
    np.testing.assert_array_equal(np.asarray(to_global(out_meta)), F.T)


@pytest.mark.parametrize("conj", [True, False])
@pytest.mark.parametrize("shape", [(24, 8), (19, 5)])
def test_panel_spread_matches_separate_redists(any_grid, shape, conj):
    """The fused one-collective panel spread must produce bitwise the same
    [MC,STAR] / [STAR,MR]-adjoint locals as the three-redistribute route it
    replaces, on every grid shape incl. ragged extents."""
    from elemental_tpu import MC, MR, VC, STAR, panel_spread

    m, k = shape
    rng = np.random.default_rng(31)
    F = rng.normal(size=(m, k)) + 1j * rng.normal(size=(m, k))
    A_vc = redistribute(from_global(F, MC, MR, grid=any_grid), VC, STAR)
    mc, mrH = panel_spread(A_vc, conj=conj)
    assert mc.dist == (MC, STAR) and mrH.dist == (STAR, MR)
    assert mc.gshape == (m, k) and mrH.gshape == (k, m)
    mc_ref = redistribute(A_vc, MC, STAR)
    mr_ref = redistribute(transpose_dist(A_vc, conj=conj), STAR, MR)
    np.testing.assert_array_equal(np.asarray(mc.local),
                                  np.asarray(mc_ref.local))
    np.testing.assert_array_equal(np.asarray(mrH.local),
                                  np.asarray(mr_ref.local))
    want = np.conj(F.T) if conj else F.T
    np.testing.assert_array_equal(np.asarray(to_global(mrH)), want)


def test_panel_spread_rejects_wrong_dist(grid24):
    from elemental_tpu import MC, MR, panel_spread

    A = from_global(f(8, 4), MC, MR, grid=grid24)
    with pytest.raises(ValueError):
        panel_spread(A)


def test_contract_mc_star(grid24):
    """Partial [MC,STAR] summed over MR comm lands on [MC,MR]."""
    import jax
    from jax.sharding import PartitionSpec as P
    from elemental_tpu import MC, MR, STAR, zeros

    F = f(9, 10)
    # every device in a grid row holds partial = F/c restricted to its rows
    c = grid24.width
    A = from_global(F / c, MC, STAR, grid=grid24)

    def fn(a):
        return engine.contract(a, MC, MR)

    out_meta = zeros(9, 10, MC, MR, grid=grid24, dtype=F.dtype)
    from elemental_tpu.core.compat import shard_map
    B = shard_map(fn, mesh=grid24.mesh, in_specs=(A.spec,),
                  out_specs=out_meta.spec, check_vma=False)(A)
    np.testing.assert_allclose(np.asarray(to_global(B)), F, rtol=1e-12)


# ---------------------------------------------------------------------
# scoped call counting + dist-metadata trace hooks (ISSUE 3 satellites)
# ---------------------------------------------------------------------

def test_redist_counts_scoped_and_isolated(grid24):
    """redist_counts() swaps a fresh counter in, readable during and
    after the block; the enclosing counter never sees inner counts."""
    from elemental_tpu import MC, MR, STAR

    F = f(8, 8)
    A = from_global(F, MC, MR, grid=grid24)
    with engine.redist_counts() as outer:
        redistribute(A, STAR, STAR)
        assert sum(outer.values()) == 1
        with engine.redist_counts() as inner:
            redistribute(A, STAR, STAR)
            redistribute(A, STAR, STAR)
            assert sum(inner.values()) == 2        # live inside the block
        assert sum(inner.values()) == 2            # and after it
        assert sum(outer.values()) == 1            # no leak outward
    assert engine.REDIST_COUNTS is not inner
    # the backward-compatible module global still counts outside any scope
    before = sum(engine.REDIST_COUNTS.values())
    redistribute(A, STAR, STAR)
    assert sum(engine.REDIST_COUNTS.values()) == before + 1


def test_redist_counter_fixture(grid24, redist_counter):
    """The pytest fixture wires the scoped counter through a test body."""
    from elemental_tpu import MC, MR, STAR

    A = from_global(f(8, 8), MC, MR, grid=grid24)
    assert sum(redist_counter.values()) == 0
    redistribute(A, STAR, STAR)
    assert redist_counter[((MC, MR), (STAR, STAR))] == 1


def test_redist_trace_records_metadata(grid24):
    """redist_trace() captures per-call dist metadata with object
    identities that prove data-flow adjacency (the analyzer's EL002
    evidence)."""
    from elemental_tpu import MC, MR, STAR, VC

    A = from_global(f(12, 12), MC, MR, grid=grid24)
    with engine.redist_trace() as log:
        V = redistribute(A, VC, STAR)
        redistribute(V, MC, MR)
    assert [r.label for r in log] == ["[MC,MR]->[VC,STAR]",
                                      "[VC,STAR]->[MC,MR]"]
    assert log[0].gshape == (12, 12) and log[0].dtype == "float64"
    assert log[1].in_id in log[0].out_ids          # fed back untouched
    assert engine._REDIST_TRACE is None            # restored on exit


# ---------------------------------------------------------------------
# the local unpack after a gather (ISSUE 29): ONE interleave primitive,
# held bit for bit to the formulas it replaced
# ---------------------------------------------------------------------

def _blocks(shape, dtype, seed):
    """Seeded blocks of ``dtype`` with a NaN and both infinities planted
    where the dtype has them."""
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    if dtype == "int8":
        return rng.integers(-128, 128, size=shape, dtype=np.int8)
    x = rng.normal(size=shape).astype(np.float32)
    flat = x.reshape(-1)
    flat[[1, flat.size // 2, flat.size - 2]] = [np.nan, np.inf, -np.inf]
    if dtype == "complex64":
        z = np.empty(shape, np.complex64)
        z.real, z.imag = x, x[..., ::-1]
        return z
    return jnp.asarray(x).astype(dtype)


def _same_bits(a, b):
    """Equal as raw bytes: the same NaN in the same place counts."""
    a, b = np.asarray(a), np.asarray(b)
    assert a.shape == b.shape and a.dtype == b.dtype
    return a.tobytes() == b.tobytes()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "complex64", "int8"])
@pytest.mark.parametrize("block", [(8, 128), (16, 256), (5, 7), (3, 1)],
                         ids=lambda b: f"{b[0]}x{b[1]}")
@pytest.mark.parametrize("dim", [0, 1])
@pytest.mark.parametrize("S", [2, 4, 8])
def test_interleave_is_the_old_formula(S, dim, block, dtype):
    """``_interleave`` against ``moveaxis + reshape``, the five hand-written
    copies it replaced, kept here as the oracle."""
    import jax.numpy as jnp
    g = jnp.asarray(_blocks((S,) + block, dtype, seed=S * 10 + dim))
    shape = list(block)
    shape[dim] *= S
    want = jnp.moveaxis(g, 0, dim + 1).reshape(shape)
    got = engine._interleave(g, dim)
    assert _same_bits(got, want)
    # index i = iLoc*S + s, spelled out
    take = np.asarray(got).take(np.arange(S) + S * (block[dim] - 1), axis=dim)
    last = np.asarray(g).take(block[dim] - 1, axis=dim + 1)
    assert _same_bits(take, np.moveaxis(last, 0, dim))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "complex64", "int8"])
@pytest.mark.parametrize("block", [(8, 128), (5, 7)],
                         ids=lambda b: f"{b[0]}x{b[1]}")
@pytest.mark.parametrize("dist", ["MC,MR", "MR,MC"])
@pytest.mark.parametrize("rc", [(2, 2), (2, 4), (4, 2)],
                         ids=lambda rc: f"{rc[0]}x{rc[1]}")
def test_interleave_2d_is_the_old_one_transpose_formula(rc, dist, block,
                                                        dtype):
    """The two-call unpack of ``_fused_to_star_star`` against the single
    4-D transpose it replaced (whose intermediate the TPU pads 64-fold)."""
    import jax.numpy as jnp
    from elemental_tpu import MC, MR
    r, c = rc
    lr, lc = block
    G = jnp.asarray(_blocks((r, c, lr, lc), dtype, seed=r * 8 + c))
    if dist == "MC,MR":
        want = G.transpose(2, 0, 3, 1).reshape(lr * r, lc * c)
        got = engine._interleave_2d(G, (MC, MR))
    else:
        want = G.transpose(2, 1, 3, 0).reshape(lr * c, lc * r)
        got = engine._interleave_2d(G, (MR, MC))
    assert _same_bits(got, want)


def _grid_of(rc):
    import jax
    from elemental_tpu import Grid
    return Grid(jax.devices()[: rc[0] * rc[1]], height=rc[0])


@pytest.mark.parametrize("shape", [(256, 512), (19, 5)],
                         ids=lambda s: f"{s[0]}x{s[1]}")
@pytest.mark.parametrize("entry", ["to_star_star", "panel_spread",
                                   "to_star_mc"])
@pytest.mark.parametrize("rc", [(2, 2), (2, 4), (4, 2)],
                         ids=lambda rc: f"{rc[0]}x{rc[1]}")
def test_gather_entries_equal_to_global_bit_for_bit(rc, entry, shape):
    """The cell's three gather entries on lane-aligned and ragged shapes,
    float32 with non-finite values planted: every device's block equals
    what ``from_global`` lays out of the same matrix."""
    from elemental_tpu import MC, MR, VC, STAR, panel_spread
    grid = _grid_of(rc)
    F = np.asarray(_blocks(shape, "float32", seed=shape[0]))
    if entry == "panel_spread":
        A = from_global(F, VC, STAR, grid=grid)
        mc, mrT = panel_spread(A, conj=False)
        assert _same_bits(mc.local, from_global(F, MC, STAR, grid=grid).local)
        assert _same_bits(mrT.local,
                          from_global(F.T, STAR, MR, grid=grid).local)
        assert _same_bits(to_global(mc), F)
        return
    dst = (STAR, STAR) if entry == "to_star_star" else (STAR, MC)
    B = redistribute(from_global(F, MC, MR, grid=grid), *dst)
    assert _same_bits(B.local, from_global(F, *dst, grid=grid).local)
    assert _same_bits(to_global(B), F)


# ---------------------------------------------------------------------
# the local filter that makes a replicated dimension distributed
# (ISSUE 32): the mirror primitive, held to numpy's strided slice
# ---------------------------------------------------------------------

@pytest.mark.parametrize("block", [(8, 128), (16, 256), (5, 7), (3, 1)],
                         ids=lambda b: f"{b[0]}x{b[1]}")
@pytest.mark.parametrize("dim", [0, 1])
@pytest.mark.parametrize("S", [2, 4])
def test_deinterleave_is_numpys_strided_slice(S, dim, block):
    """``_deinterleave`` under ``jit`` with a TRACED shift, as the engine
    calls it (the shift is a device's rank), on whole-tile and ragged
    blocks: the slice ``shift::S`` of ``dim``, bit for bit, with NaN, both
    infinities and -0.0 planted."""
    import jax
    shape = list(block)
    shape[dim] *= S
    x = np.array(_blocks(tuple(shape), "float32", seed=S * 10 + dim))
    x.reshape(-1)[[0, x.size // 3, x.size - 1]] = -0.0
    f = jax.jit(lambda x, shift: engine._deinterleave(x, dim, S, shift))
    for shift in range(S):
        want = x[shift::S] if dim == 0 else x[:, shift::S]
        assert _same_bits(f(x, np.int32(shift)), want)
    # and the inverse of the interleave: block s back out of the S blocks
    g = _blocks((S,) + block, "float32", seed=S + dim)
    for s in range(S):
        assert _same_bits(
            engine._deinterleave(engine._interleave(g, dim), dim, S, s), g[s])


@pytest.mark.parametrize("shape", [(32, 1024), (16, 512), (19, 5), (13, 64)],
                         ids=lambda s: f"{s[0]}x{s[1]}")
@pytest.mark.parametrize("rc", [(2, 2), (1, 4)],
                         ids=lambda rc: f"{rc[0]}x{rc[1]}")
def test_lu_row_block_chain_round_trips(rc, shape):
    """The LU step's row block, as ``lapack/lu.py`` composes it:
    [MC,MR] -> [STAR,VR] -> [STAR,MR] -> [MC,MR] in ONE jitted function with
    the [STAR,MR] block returned beside the write-back (the lane interleave
    feeds the row filter there): every hop equals ``from_global`` of the
    same matrix and the round trip equals its input, bit for bit."""
    import jax
    from elemental_tpu import MC, MR, VR, STAR
    grid = _grid_of(rc)
    F = np.array(_blocks(shape, "float32", seed=shape[1]))
    F.reshape(-1)[[0, F.size - 1]] = -0.0
    A = from_global(F, MC, MR, grid=grid)

    @jax.jit
    def chain(a):
        vr = redistribute(a, STAR, VR)
        mr = redistribute(vr, STAR, MR)
        return vr, mr, redistribute(mr, MC, MR)
    vr, mr, back = chain(A)
    assert _same_bits(vr.local, from_global(F, STAR, VR, grid=grid).local)
    assert _same_bits(mr.local, from_global(F, STAR, MR, grid=grid).local)
    assert _same_bits(back.local, A.local)
    # to_global itself gives +0.0 for -0.0: equal, not the same bits
    np.testing.assert_array_equal(np.asarray(to_global(back)), F)
