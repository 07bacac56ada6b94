"""The three fused panel kernels, compiled by the TPU's own compiler for
a DESCRIBED v5e chip (no chip attached, nothing runs): at the widths
``chip_smoke.py`` gives them and at the largest shapes their VMEM gate
admits; the one-pass triangle ``symv`` at the eigensolve cell's views;
and the unpivoted block LU at the sub-block order ``lu._lu_nopiv`` ships.
A kernel body that Mosaic refuses -- a primitive with no lowering, a
slice off the (8, 128) tiling, more VMEM than the compiler grants -- fails here, at no chip time.  And the redistribution engine's
local unpacks at the 2x2 benchmark cell's shapes, for the described 2x2:
a relayout the compiler pads 64-fold shows as temporary bytes.

The topology is described inside a module-scoped fixture (never at
import), and every compile happens in this process: only one process may
hold the TPU library at a time.
"""
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from elemental_tpu.kernels import (PANEL_VMEM_BUDGET, PanelPlan,
                                   lu_nopiv_block, lu_panel, potrf_inv,
                                   qr_panel, symv_lower)
from elemental_tpu.kernels.symv import shard_block, symv_lower_shard

PLAN = PanelPlan(impl="pallas")


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_compile_cache():
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep it out
    from jax.experimental.compilation_cache import compilation_cache as cc
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


def _compile(fn, *shapes, one_chip):
    args = [jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
            for shape in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


#: (kernel, shape the gate is asked about, copies it is asked with) -- the
#: call sites' own figures (lapack/lu.py, cholesky.py, qr.py)
_LU = lambda p: lu_panel(p, p.shape[1], inner=PLAN.pallas_inner,     # noqa: E731
                         interpret=False)
_LU_UNB = lambda p: lu_panel(p, p.shape[1], inner=0, interpret=False)  # noqa: E731
_CHOL = lambda d: potrf_inv(d, interpret=False)                      # noqa: E731
_QR = lambda p: qr_panel(p, interpret=False)                         # noqa: E731


@pytest.mark.parametrize("fn,shape,copies", [
    # the widths chip_smoke.py's kernel phase uses (N = 2048, nb = 256)
    pytest.param(_LU, (2048, 256), 3, id="lu-smoke-first-panel"),
    pytest.param(_LU, (256, 256), 3, id="lu-smoke-last-panel"),
    pytest.param(_LU_UNB, (2048, 256), 3, id="lu-unblocked"),
    pytest.param(_CHOL, (256, 256), 4, id="chol-smoke"),
    pytest.param(_QR, (2048, 256), 4, id="qr-smoke-first-panel"),
    # the largest shapes the gate admits, per width
    pytest.param(_LU, (5456, 256), 3, id="lu-gate-max-256"),
    pytest.param(_CHOL, (1024, 1024), 4, id="chol-gate-max"),
    pytest.param(_QR, (4096, 256), 4, id="qr-gate-max-256"),
    pytest.param(_QR, (2048, 512), 4, id="qr-gate-max-512"),
    pytest.param(_QR, (1024, 1024), 4, id="qr-gate-max-1024"),
])
def test_kernel_compiles_for_v5e(fn, shape, copies, one_chip):
    assert PLAN.use_pallas(shape, jnp.float32, copies=copies), \
        "the gate sends this shape to XLA; the case no longer tests a kernel"
    _compile(fn, shape, one_chip=one_chip)


@pytest.mark.parametrize("nt", [16384, 768, 257])
def test_symv_kernel_compiles_for_v5e(nt, one_chip):
    """The eigensolve cell's first view and a late one (768 is no multiple
    of the 512-tile: a ragged edge), and an order that is no multiple of
    anything (one edge block past both extents)."""
    _compile(lambda a, x: symv_lower(a, x, interpret=False), (nt, nt), (nt,),
             one_chip=one_chip)


@pytest.mark.parametrize("nt", [16384, 1792, 256, 399])
def test_symv_shard_kernel_compiles_for_v5e(nt, one_chip):
    """The shard form of the same body (ISSUE 52), for one chip of a 2x2
    grid, ``(p, q)`` traced scalars as under ``shard_map``: the grid cell's
    first local view (8192) and its last (128), a local order that is no
    multiple of the 512-tile (896), and an odd global order (a local order
    of 200 with a line past the matrix on the chips of residue 1)."""
    m, (block, _tile) = -(-nt // 2), shard_block(nt, 2)
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in (((m, m), jnp.float32),
                                 ((2 * block,), jnp.float32),
                                 ((), jnp.int32), ((), jnp.int32))]
    text = jax.jit(lambda a, x, p, q: symv_lower_shard(
        a, x, p, q, stride=2, nt=nt, interpret=False)).lower(
            *args).compile().as_text()
    assert "tpu_custom_call" in text
    # read as stored: no transposing copy of the shard before the kernel
    assert not re.search(rf"f32\[{m},{m}\]\S* (copy|transpose)\(", text)


#: the sub-block order ``lu._lu_nopiv`` ships (its ``bs`` default)
NOPIV_BS = 256


@pytest.mark.parametrize("n", [NOPIV_BS, 200])
def test_lu_nopiv_block_kernel_compiles_for_v5e(n, one_chip):
    """The sub-block order ``lu._lu_nopiv`` hands it (the HPL-MxP cell's
    128 launches) and a ragged one (200 = 25 sublane tiles, 72 columns
    past a lane tile: padded)."""
    _compile(lambda b: lu_nopiv_block(b, interpret=False), (n, n),
             one_chip=one_chip)


@pytest.mark.parametrize("shape,copies", [
    ((5464, 256), 3), ((1152, 1152), 4), ((4104, 256), 4)])
def test_gate_refuses_the_next_shape_up(shape, copies):
    # the cases above are the gate's corners: one tile more and it says no
    assert not PLAN.use_pallas(shape, jnp.float32, copies=copies)
    assert copies * shape[0] * shape[1] * 4 > PANEL_VMEM_BUDGET


def test_compilers_default_vmem_grant_refuses_a_gate_corner(one_chip,
                                                            monkeypatch):
    # why every panel kernel sets vmem_limit_bytes: under the compiler's
    # own default the largest Cholesky block the gate admits is refused
    from elemental_tpu.kernels import chol_panel
    from jax.experimental.pallas import tpu as pltpu
    monkeypatch.setattr(chol_panel, "compiler_params",
                        lambda: pltpu.CompilerParams())
    with pytest.raises(Exception, match="(?i)vmem"):
        # a fresh function: jit would hand back the earlier compile of _CHOL
        _compile(lambda d: potrf_inv(d, interpret=False), (1024, 1024),
                 one_chip=one_chip)


# ---------------------------------------------------------------------
# the engine's local unpacks on the described 2x2 (ISSUE 29): what a
# relayout costs shows here as the bytes the compiler plans beside the
# operands -- a minor dimension of 2 padded to 128 lanes is 64 times them
# ---------------------------------------------------------------------

@pytest.fixture(scope="module")
def grid22(topo):
    import elemental_tpu as el
    return el.Grid(topo.devices, height=2)


def _abstract(grid, m, n, cdist, rdist):
    from elemental_tpu.core.distmatrix import DistMatrix
    meta = DistMatrix(None, (m, n), cdist, rdist, 0, 0, grid)
    return meta.with_local(jax.ShapeDtypeStruct(
        (m, n), jnp.float32, sharding=grid.sharding(meta.spec)))


_SHAPE = re.compile(r"\b(pred|[a-z]+\d+)\[([\d,]+)\]\{([\d,]+)(?::([^}]*))?\}")


def _padded_small_minor(text, over=64 << 20, under=8):
    """``[(shape with layout, bytes with padding)]`` of every array in an
    optimized HLO text that takes more than ``over`` bytes once its two
    minor-most dimensions are rounded up to its tile, and whose minor-most
    dimension has an extent below ``under``: the signature of a relayout
    the TPU compiler pads (an ``f32[1024,2,8192]{1,0,2:T(8,128)}`` holds
    its dimension of 2 on 128 lanes, 4.29 GB for 67 MB)."""
    found = set()
    for shape in _SHAPE.finditer(text):
        dtype, dims, order, tiling = shape.groups()
        dims = [int(d) for d in dims.split(",")]
        order = [int(d) for d in order.split(",")]
        if len(order) != len(dims) or dims[order[0]] >= under:
            continue
        tile = re.match(r"T\((\d+)(?:,(\d+))?\)", tiling or "")
        tile = [int(t) for t in tile.groups() if t][::-1] if tile else []
        n = 1 if dtype == "pred" else int(re.sub(r"\D", "", dtype)) // 8
        for i, d in enumerate(order):
            t = tile[i] if i < len(tile) else 1
            n *= -(-dims[d] // t) * t
        if n > over:
            found.add((shape.group(0), n))
    return sorted(found)


def test_the_layout_reader_counts_padding():
    text = """
  %copy.6 = f32[2,2048,4096]{1,0,2:T(2,128)S(1)} copy(%bitcast.10)
  %reshape.12 = f32[1024,2,8192]{1,0,2:T(8,128)} reshape(%bitcast.9)
  %t = (f32[2048,4096,2]{2,1,0:T(8,128)}, u32[]{:T(128)}) all-gather-start(%p)
  %small = f32[16,2,128]{1,0,2:T(8,128)} reshape(%q)
  %bf = bf16[8192,8192,1]{2,1,0:T(8,128)(2,1)} copy(%r)
"""
    assert _padded_small_minor(text) == [
        ("bf16[8192,8192,1]{2,1,0:T(8,128)(2,1)}", 8192 * 8192 * 128 * 2),
        ("f32[1024,2,8192]{1,0,2:T(8,128)}", 1024 * 128 * 8192 * 4),
        ("f32[2048,4096,2]{2,1,0:T(8,128)}", 2048 * 4096 * 128 * 4)]


def _plan(fn, *args):
    """``(temporary bytes, padded small-minor arrays)`` of ``fn`` compiled
    for the described chips."""
    compiled = jax.jit(fn).lower(*args).compile()
    return (compiled.memory_analysis().temp_size_in_bytes,
            _padded_small_minor(compiled.as_text()))


@pytest.mark.parametrize("n", [2048, 4096])
def test_gather_to_star_star_unpacks_in_whole_tiles(n, grid22):
    """The diagonal block and the crossover tail of the 2x2 cell: the one
    4-D transpose this replaced planned 64 times the block (1.07 GB and
    4.29 GB) for its lane-padded intermediate."""
    import elemental_tpu as el
    A = _abstract(grid22, n, n, el.MC, el.MR)
    temp, padded = _plan(
        lambda a: el.redistribute(a, el.STAR, el.STAR).local, A)
    assert temp <= 2 * n * n * 4, temp
    assert not padded


def test_panel_spread_then_column_filter_stays_unpadded(grid22):
    """Step 0 of the cell's Cholesky: the spread's row interleave feeds the
    [MC,STAR] -> [MC,MR] write-back, a lane de-interleave.  Merged into one
    reshape the pair planned 7.5 GB twice and the program no longer fitted
    the chip; ``_deinterleave`` keeps them apart."""
    import elemental_tpu as el
    m, k = 30720, 2048
    A = _abstract(grid22, m, k, el.VC, el.STAR)

    def step(a):
        mc, mr = el.panel_spread(a)
        return el.redistribute(mc, el.MC, el.MR).local, mr.local
    temp, padded = _plan(step, A)
    assert temp <= 4 * m * k * 4, temp
    assert not padded


@pytest.mark.parametrize("front", [False, True],
                         ids=["chain-alone", "behind-hop-and-matmul"])
@pytest.mark.parametrize("width", [16384, 14336, 12288])
def test_lu_row_block_chain_stays_unpadded(width, front, grid22):
    """Steps 0 to 2 of the 2x2 LU cell's row block, COMPOSED as
    ``lapack/lu.py`` composes it (ISSUE 32): [STAR,VR] -> [STAR,MR], whose
    result is both returned (the update reads it) and written back through
    [STAR,MR] -> [MC,MR]; with or without the [MC,MR] -> [STAR,VR] hop and
    the 2048^2 ``Li11`` matmul in front.  Alone each entry plans at most
    the block; composed, the partial gather's lane interleave fed the row
    filter, the compiler merged the two reshapes into one whose minor
    dimension was the grid's 2, and the chain planned 8,589,934,592 bytes
    at width 16384: two ``f32[1024,2,8192]{1,0,2:T(8,128)}`` of 4.29 GB for
    a 67 MB block.  Entries are rehearsed as the driver composes them, with
    every consumer of a result returned."""
    import elemental_tpu as el
    from elemental_tpu.core.distmatrix import DistMatrix
    nb = 2048
    block = nb * width * 4 // 2             # float32 bytes of [STAR,MR] a device

    def chain(a, li11):
        if front:
            a = el.redistribute(a, el.STAR, el.VR)
            u = jnp.matmul(li11, a.local, precision=jax.lax.Precision.HIGHEST)
            a = DistMatrix(u, a.gshape, el.STAR, el.VR, 0, 0, grid22)
        mr = el.redistribute(a, el.STAR, el.MR)
        return el.redistribute(mr, el.MC, el.MR).local, mr.local
    A = _abstract(grid22, nb, width,
                  *((el.MC, el.MR) if front else (el.STAR, el.VR)))
    li11 = jax.ShapeDtypeStruct(
        (nb, nb), jnp.float32,
        sharding=grid22.sharding(jax.sharding.PartitionSpec()))
    temp, padded = _plan(chain, A, li11)
    assert temp <= 4 * block, temp
    assert not padded


_BIG_MOVE = re.compile(r" = \w+\[([\d,]+)\]\{[^}]*\} (copy|slice|select)\(")


def _whole_matrix_moves(text, least, kinds=("copy", "slice")):
    """Shapes of the ``copy`` / ``slice`` instructions (or of the ``kinds``
    asked for) of an optimized HLO text whose result holds at least
    ``least`` elements."""
    found = []
    for move in _BIG_MOVE.finditer(text):
        dims = [int(d) for d in move.group(1).split(",")]
        if move.group(2) in kinds and math.prod(dims) >= least:
            found.append((move.group(2), dims))
    return found


def test_the_move_reader_counts_whole_matrix_copies():
    text = """
  %slice.174 = f32[14336,14336]{1,0:T(8,128)} slice(%A_local.1), slice={[2048:16384], [2048:16384]}
  %copy.203 = f32[14336,14336]{0,1:T(8,128)} copy(%slice.174)
  %slice.9 = f32[12288,2048]{0,1:T(8,128)} slice(%fusion.3), slice={[0:12288], [0:2048]}
  %fusion.44 = f32[14336,14336]{0,1:T(8,128)} fusion(%copy.203, %custom-call.424), kind=kOutput
  %copy-start.2 = (f32[16384,8]{0,1}, f32[16384,8]{0,1}, u32[]) copy-start(%copy.5)
"""
    assert _whole_matrix_moves(text, 12288 ** 2) == [
        ("slice", [14336, 14336]), ("copy", [14336, 14336])]


def _plan_bytes(compiled):
    """What the benchmark's ``plan_gb`` reads, in bytes."""
    mem = compiled.memory_analysis()
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)


def test_one_chip_cholesky_factors_in_one_buffer(topo):
    """The one-chip ``hpd_solve`` whole, A donated as the benchmark donates
    it, at N = 16384, nb = 2048 (ISSUE 34; at N = 4096 the compiler keeps
    the buffers in ``S(1)`` and the size shows nothing).  The blocked loop
    copied the shrinking trailing matrix at every step: three ``copy`` /
    ``slice`` of at least (n - 2 nb)^2 elements here (14336^2 twice,
    12288^2), 41.6 GB of traffic a solve at N = 32768, and a plan of
    3.612109 GB.  In one buffer it holds ONE, the copy of the operand that
    cannot be aliased (the result is n x 8), and plans 2.493894144 GB: the
    operand, the working buffer and nothing else of their size.  The bound
    is that reading and 1 % (a quarter of an operand is 0.27 GB).  A second
    whole-matrix temporary beside the working buffer, a masked or re-laid
    copy of the factor for the sweeps, reads 4.16 GB here; without the
    layout pin of ``_local_cholesky`` (zeros above the panels kept) the
    sweeps' ``copy f32[1,16384,1,16384]`` is back: two moves, 4.207338496
    GB, over the parent's."""
    import elemental_tpu as el
    n, nb, nrhs = 16384, 2048, 8
    grid = el.Grid([topo.devices[0]])
    A = _abstract(grid, n, n, el.MC, el.MR)
    B = _abstract(grid, n, nrhs, el.MC, el.MR)
    compiled = jax.jit(lambda a, b: el.hpd_solve(a, b, nb=nb),
                       donate_argnums=0).lower(A, B).compile()
    moves = _whole_matrix_moves(compiled.as_text(), (n - 2 * nb) ** 2)
    assert len(moves) <= 1, moves
    assert _plan_bytes(compiled) <= 2_520_000_000, _plan_bytes(compiled)


_ENTRY_F32 = re.compile(r"^\s*(?:ROOT )?%[\w.\-]+ = f32\[(\d+),(\d+)\]\{",
                        re.M)


@pytest.mark.parametrize("driver,nrhs,donate,bound,joined", [
    pytest.param("hpd_solve", 8, (0,), 642_300_000, True, id="hpd_solve"),
    pytest.param("mixed_solve", 1, (), 617_400_000, False,
                 id="mixed_solve")])
def test_blocked_panel_products_leave_no_second_panel(driver, nrhs, donate,
                                                      bound, joined, topo):
    """The one-chip cells' programs at N = 8192, nb = 2048 (three steps with
    a panel under their diagonal block; 45 s each), ISSUE 47: a panel's
    product with a triangular inverse is four block matmuls now
    (``lu._tri_matmul``), and four values joined can leave a SECOND
    panel-sized temporary beside the panel (6144 x 2048 float32 is 50 MB
    here, 8 % of either plan; 252 MB at the cells' N = 32768, where the
    benchmark's ``plan_gb`` has a 1 % bound).  The bounds are this tree's
    readings and 1 %: 635,844,608 bytes for ``hpd_solve`` (L21 joined ONCE
    a step, row-major, after the step's stripes: ``cholesky.
    _local_chol_array``; its N = 16384 reading is held by the test above),
    611,203,072 for ``mixed_solve`` (648,960,000 before: the panels go back
    by plain update-slices, and step 0's bounds select with a float32
    panel of its own went).  In ``mixed_solve`` the join never exists in
    float32: the working buffer's write and the bfloat16 rounding read the
    four blocks, so NO float32 value of a panel's size is left in the
    program but the blocks' own."""
    import elemental_tpu as el
    from elemental_tpu import obs
    n, nb = 8192, 2048
    grid = el.Grid([topo.devices[0]])
    A = _abstract(grid, n, n, el.MC, el.MR)
    B = _abstract(grid, n, nrhs, el.MC, el.MR)
    solve = getattr(el, driver)
    with obs.metrics_scope() as reg:
        compiled = jax.jit(lambda a, b: solve(a, b, nb=nb),
                           donate_argnums=donate).lower(A, B).compile()
    ticks = dict(reg.counters("panel_tri_product"))
    assert set(ticks) == {("panel_tri_product", (("kind", "blocked"),))}
    assert _plan_bytes(compiled) <= bound, _plan_bytes(compiled)
    entry = compiled.as_text().split("\nENTRY ")[1]
    panels = [(r, c) for r, c in
              ((int(r), int(c)) for r, c in _ENTRY_F32.findall(entry))
              if r * c >= (n - 2 * nb) * nb and (r, c) != (n, n)]
    assert bool(panels) == joined, panels


def _donated_hpd_solve_on_2x2(grid22, n, nb=2048, nrhs=8):
    """The whole ``hpd_solve`` as the 2x2 benchmark cells run it, A
    donated, compiled for the described ``v5e:2x2``."""
    import elemental_tpu as el
    A = _abstract(grid22, n, n, el.MC, el.MR)
    B = _abstract(grid22, n, nrhs, el.MC, el.MR)
    return jax.jit(lambda a, b: el.hpd_solve(a, b, nb=nb),
                   donate_argnums=0).lower(A, B).compile()


def test_grid_cholesky_holds_one_working_shard(grid22):
    """The 2x2 ``hpd_solve`` whole at N = 16384 (ISSUE 35; 50 s; the same
    buffers as at N = 65536, which takes four minutes: the slow test
    below).  Before, the program held A's shard (a parameter: the result
    is n x 8, so the donated operand aliases nothing), two working shards
    (the compiler copied the whole shard once a step: six ``copy
    f32[1,8192,1,8192]``), the exit mask's ``select`` of the whole factor
    and, a step, the trailing window sliced out for the update: ten
    ``copy`` / ``slice`` / ``select`` of at least (n/2 - nb)^2 elements and
    a plan of 1,363,257,856 bytes, 5.08 shards.  Now: the copy of A, the
    entry mask that makes the working shard of it, and the two that stand
    INSIDE step 0's update fusion (its window read where it lies, and the
    mask on the product): four, and 844,633,600 bytes, 3.15 shards.  The
    bound is that reading and 1 %; a second working shard reads 1.11 GB.
    Since ISSUE 36 the update walks stripes of the lower trapezoid, twelve
    matmuls for six: none of them reaches the count's size, two moves are
    left (the copy of A and the entry mask) and the plan reads 846,343,168
    bytes.  It read 1,143,753,728 with stripes whose products were as wide
    as tall or wider (row stripes; column stripes with a square corner): the
    compiler then lays the working shard out ROW-major, drops the copy of
    A, and ``memory_analysis()`` reads one shard more."""
    n, nb = 16384, 2048
    compiled = _donated_hpd_solve_on_2x2(grid22, n, nb)
    text = compiled.as_text()
    moves = _whole_matrix_moves(text, (n // 2 - nb) ** 2,
                                kinds=("copy", "slice", "select"))
    assert len(moves) <= 4, moves
    assert not _padded_small_minor(text)
    assert _plan_bytes(compiled) <= 853_000_000, _plan_bytes(compiled)


_ENTRY_OP = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = \w+\[[\d,]*\]\{([\d,]*)"
                       r"[^ ]* ([\w\-]+)\(.*op_name=\"([^\"]*)\"", re.M)


def test_grid_cholesky_panel_reaches_the_spread_row_major(grid22):
    """The 2x2 ``hpd_solve`` at N = 8192, nb = 2048 (two panels on the
    grid before the crossover tail; 30 s), ISSUE 47.  The TPU compiler gave
    the panel's ONE dense matmul a row-major result and the panel spread
    reads it so; ``lu._tri_matmul``'s block products come column-major,
    and the spread then re-laid every panel: two ``copy`` a step under the
    spread's name (28 at N = 32768), 0.6 ms a step on the chip,
    ``hpd32k.2x2.b2b`` 0.24516 -> 0.24734 s with the products' own 0.0076 s
    saved (PERF.md 6, PR 47).  The grid loop pins L21 row-major on a TPU as
    the one-chip loop does: every panel product is row-major and no
    ``copy`` stands under a spread."""
    compiled = _donated_hpd_solve_on_2x2(grid22, 8192)
    entry = compiled.as_text().split("\nENTRY ")[1]
    ops = _ENTRY_OP.findall(entry)
    products = [layout for layout, _op, name in ops
                if "/panel/" in name and name.endswith("/dot_general")]
    assert len(products) >= 8 and set(products) == {"1,0"}, products
    assert not [name for _layout, op, name in ops
                if op == "copy" and "/spread/" in name]


@pytest.mark.slow
def test_north_star_size_fits_the_2x2_host(grid22):
    """N = 65536 on 2x2, the ``hpd64k.2x2.b2b`` cell's program (ISSUE 35;
    four to five minutes here, so not in tier-1: by hand, ``-m slow``).
    Before ISSUE 35 it was refused: "Used 16.15G of 15.75G hbm".  It planned
    13,032,259,072 bytes a device with full-square updates, 3.03 shards of
    4.29 GB, and plans 13,049,128,960 with stripes (ISSUE 36); the bound is
    the benchmark's 1 % on the first, which is what ``plan_gb`` is held to
    in ``hpd64k.2x2.b2b``."""
    compiled = _donated_hpd_solve_on_2x2(grid22, 65536)
    assert _plan_bytes(compiled) <= 13_163_000_000, _plan_bytes(compiled)


def test_move_rows_plans_half_a_shard_and_one_all_reduce(grid22):
    """A panel step's pivot swaps at the 2x2 LU cell's size (ISSUE 31):
    ``move_rows`` of 4096 rows of a [MC,MR] 16384 x 16384 operand, whose
    cross-device motion the partitioner plans.  It plans 134,549,504 bytes
    of temporaries beside the 268,435,456-byte shard (0.501 of it: the
    moved rows at the shard's width, ``f32[4096,8192]``), and the only
    collective it inserts is ONE all-reduce of those rows over the grid's
    column, named after the gather it was made from (so the trace books it
    under ``el.redist.row_permute``).  The bound is a guard against growth:
    a gather or scatter the partitioner answers by replicating the operand
    would plan a whole matrix, four shards."""
    import elemental_tpu as el
    from elemental_tpu.redist.engine import move_rows
    n, k = 16384, 4096
    A = _abstract(grid22, n, n, el.MC, el.MR)
    idx = jax.ShapeDtypeStruct((k,), jnp.int32)
    valid = jax.ShapeDtypeStruct((k,), jnp.bool_)
    compiled = jax.jit(
        lambda a, t, s, v: move_rows(a, t, s, v).local).lower(
            A, idx, idx, valid).compile()
    shard = n * n * 4 // 4                  # float32 bytes on each of four
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= 0.6 * shard, temp
    text = compiled.as_text()
    assert not _padded_small_minor(text)
    collectives = {c: text.count(f" {c}(") + text.count(f" {c}-start(")
                   for c in ("all-gather", "all-to-all", "all-reduce",
                             "collective-permute")}
    assert collectives == {"all-gather": 0, "all-to-all": 0, "all-reduce": 1,
                           "collective-permute": 0}
    reduce_line = next(line for line in text.split("\n")
                       if " all-reduce(" in line)
    assert "el.redist.row_permute" in reduce_line


def test_eigensolve_column_loop_reads_the_view_once_and_moves_nothing(topo):
    """The whole donated ``jit(herm_eig)`` at n = 1024, nb = 256, for ONE
    described v5e chip (ISSUE 38, ISSUE 44; a minute).  Before PR 38 the TPU
    compiler re-laid the panel's FIXED trailing view out inside the
    ``while`` body, ``copy f32[nt,nt]{1,0} -> {0,1}`` once a COLUMN (34.2 of
    the 41.9 s of ``heig.1x1.b2b``; the CPU backend assigns layouts
    otherwise and never showed it); PR 38's one ``gemv`` against a mirrored
    view read the full square, twice the stored triangle's bytes (7.86 of
    9.69 s).  On one TPU chip every column loop now holds exactly ONE
    ``tpu_custom_call``, the one-pass triangle ``symv`` kernel under
    ``k<panel>/hemv``, which is the view's only reader: no ``copy``,
    ``transpose``, ``select`` or ``slice`` makes an nt x nt array there, no
    fusion takes one as a parameter, no mirror's transposing exchange is
    left in the reduction, and the kernel's row-major operand costs no
    relayout anywhere: the compiler holds the working matrix COLUMN-major,
    the kernel reads its operand through the transpose, and the program has
    no ``copy`` or ``transpose`` of a panel's view at all (read as stored it
    had one a panel, ``copy f32[nt,nt]{1,0}``, with no name)."""
    import elemental_tpu as el
    from elemental_tpu import obs
    from .lapack.test_herm_eig_compiled import column_loops, square_ops
    n, nb = 1024, 256
    grid = el.Grid([topo.devices[0]])
    A = _abstract(grid, n, n, el.MC, el.MR)
    with obs.metrics_scope() as reg:
        text = jax.jit(lambda a: el.herm_eig(a, nb=nb),
                       donate_argnums=0).lower(A).compile().as_text()
    assert dict(reg.counters("herm_tridiag_hemv")) == {
        ("herm_tridiag_hemv", (("impl", "symv"),)): 4}
    assert not reg.counters("herm_tridiag_symmetrize")
    assert not re.search(
        r'op_name="[^"]*/el\.hermitian_tridiag/[^"]*el\.redist\.MR_MC\.to\.MC_MR',
        text)
    everything = [(None, line.strip()) for line in text.splitlines()]
    loops = column_loops(text)
    assert sorted(loops) == [0, 1, 2, 3]
    for k, lines in loops.items():
        nt = n - k * nb
        kernels = [line for _c, line in lines if "tpu_custom_call" in line]
        assert len(kernels) == 1, (k, kernels)
        assert re.match(r"%?el_symv_lower[.\d]* = ", kernels[0]), kernels[0]
        assert re.search(rf'op_name="[^"]*/k{k:02d}/hemv/', kernels[0])
        assert f"f32[{nt},{nt}]{{1,0}}" in kernels[0]      # row-major operand
        # the one-chip form of the body (ISSUE 52 gave it a shard form with
        # two more scalar words): two tile tables, the view, the vector
        assert len(re.search(r"custom-call\(([^)]*)\)", kernels[0])
                   .group(1).split(", ")) == 4, kernels[0]
        if nt == nb:                    # the panel's own blocks are nt x nt
            continue
        assert not square_ops(
            lines, nt, ("copy", "transpose", "select", "slice")), k
        assert not square_ops(
            [(c, line) for c, line in lines if "fused_computation" in c],
            nt, ("parameter",)), k
        if k:               # (nt = n is also the order of A, Z and their copies)
            assert not square_ops(everything, nt, ("copy", "transpose")), k


def test_grid_eigensolve_column_reads_the_local_view_once_and_copies_no_shard(
        grid22):
    """The grid twin of the test above (ISSUE 50, ISSUE 52): the whole
    donated ``jit(herm_eig)`` at n = 2048, nb = 256 for the described
    ``v5e:2x2``, float32 throughout as on the chip (a minute).  On a SQUARE
    grid of TPU chips every panel takes the grid form of the one-pass
    triangle ``symv`` (``herm_tridiag_hemv{impl=symv_grid}``): nothing is
    mirrored (no ``herm_tridiag_symmetrize``, no ``MR_MC.to.MC_MR``
    exchange under the reduction), and a column is ONE ``tpu_custom_call``
    under ``k<panel>/hemv``, each chip's kernel on its (nt/2, nt/2) shard
    of the view AS STORED (the compiler holds the grid's working shard
    ROW-major, so the kernel walks the tiles on or below the shard's own
    diagonal; through the transpose, as on one chip, every panel paid a
    transposing ``copy f32[nt/2,nt/2]``, which only this program showed),
    and ONE collective, the all-reduce of the replicated vector, under
    ``el.redist.hemv_join`` and ``k<panel>/hemv``.  Before (PR 38's mirror
    path on every grid): a multiply-reduce over the full local square and
    five dependent collectives a column, 1.54 + 0.33 + 0.44 of
    ``heig.2x2.b2b``'s 3.41 s.  Still no ``copy``, ``transpose``,
    ``select``, ``slice`` or update makes a local view's worth of data in
    any loop body, and NO fusion takes the view as a parameter: the kernel
    is its only reader.  The plan beside the shard: under 6 shards as
    before (3.6 here).

    ISSUE 51, the same program: its three distributed merges (two of 512,
    one of 1024) are six ``gemm`` products, every one ``slice``, whose
    hops move blocks through the engine's fused kernels.  Under ANY name
    no ``gather`` and no ``scatter`` moves a device's share of the matrix,
    n^2 / 4 entries, or more (through the plan executor's index tables
    each product packed and unpacked an entry at a time: 1.94 of
    ``heig.2x2.b2b``'s 5.58 s), and nothing under a merge holds a minor
    dimension of 2 on 128 lanes (an interleave merged with a neighbour's
    reshape: the local matmul, ``interior_view``, ``interior_update``)."""
    import elemental_tpu as el
    from elemental_tpu import obs
    from .lapack.test_herm_eig_compiled import (COLLECTIVE, big_moves,
                                                column_loops, square_ops)
    n, nb = 2048, 256
    A = _abstract(grid22, n, n, el.MC, el.MR)
    with jax.enable_x64(False), obs.metrics_scope() as reg:
        compiled = jax.jit(lambda a: el.herm_eig(a, nb=nb),
                           donate_argnums=0).lower(A).compile()
    assert dict(reg.counters("herm_tridiag_hemv")) == {
        ("herm_tridiag_hemv", (("impl", "symv_grid"),)): n // nb}
    assert not reg.counters("herm_tridiag_symmetrize")
    text = compiled.as_text()
    assert not re.search(
        r'op_name="[^"]*/el\.hermitian_tridiag/[^"]*el\.redist\.MR_MC\.to\.MC_MR',
        text)
    assert text.count('custom_call_target="tpu_custom_call"') == n // nb
    assert dict(reg.counters("dc_merge"))[
        "dc_merge", (("kind", "distributed"),)] == 3
    assert dict(reg.counters("gemm_route")) == {
        ("gemm_route", (("alg", "slice"),)): 6}
    assert not big_moves(text, n * n // 4)
    merges = "\n".join(line for line in text.splitlines()
                       if re.search(r'op_name="[^"]*/merge/', line))
    assert merges.count("/el.gemm/") > 100
    assert not _padded_small_minor(merges, over=1 << 20)
    loops = column_loops(text)
    assert sorted(loops) == list(range(n // nb))
    for k, lines in loops.items():
        nt = (n - k * nb) // 2                   # the local view's order
        found = [(m.group(1), line) for _c, line in lines
                 for m in [COLLECTIVE.search(line)] if m]
        # ONE collective a column: the all-reduce of a vector, named
        ((op, join),) = found
        assert op == "all-reduce", (k, op)
        assert re.search(rf'op_name="[^"]*/k{k:02d}/hemv/[^"]*'
                         r'el\.redist\.hemv_join/', join), join
        assert re.match(r"%?[\w.\-]+ = f32\[\d+\]", join), join
        # ONE kernel a column, the view's only reader, on the shard as
        # stored (row-major, no transpose before it)
        kernels = [line for _c, line in lines if "tpu_custom_call" in line]
        assert len(kernels) == 1, (k, kernels)
        assert re.match(r"%?el_symv_lower[.\d]* = ", kernels[0]), kernels[0]
        assert re.search(rf'op_name="[^"]*/k{k:02d}/hemv/', kernels[0])
        assert f"f32[{nt},{nt}]{{1,0}}" in kernels[0]
        readers = {c for c, line in lines if "fused_computation" in c
                   and re.search(rf"= f32\[{nt},{nt}\]\S* parameter\(", line)}
        assert not readers, (k, readers)
        if nt == nb:                    # the panel's own blocks are nt x nt
            continue
        assert not square_ops(
            [(c, line) for c, line in lines if "fused_computation" not in c],
            nt, ("copy", "transpose", "select", "slice", "fusion",
                 "dynamic-update-slice", "all-to-all", "all-gather")), k
        # nor anywhere in the program: the kernel's operand costs no
        # relayout of a panel's view (nt = n/2 is also the order of A's
        # shard, Z's and their copies)
        if k:
            assert not square_ops([(None, line.strip())
                                   for line in text.splitlines()],
                                  nt, ("copy", "transpose")), k
    mem = compiled.memory_analysis()
    plan = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert plan < 6 * (4 * n * n // 4), plan
    # what the program costs the persistent compile cache (the chip
    # machine takes no entry over 192 MiB, and with every merge unrolled
    # the cell's program, 214 MB, was compiled by every run): 16.3 MB
    # here with the two merges of 512 unrolled, 154.6 MB at the cell's n
    # with a level rolled into one loop (perf/program_size.py)
    from perf.program_size import CACHE_ENTRY_LIMIT, entry_bytes
    assert entry_bytes(compiled)[1] < CACHE_ENTRY_LIMIT // 10


def test_mixed_solve_factors_its_diagonal_blocks_in_vmem(topo):
    """The whole ``jit(mixed_solve)`` at n = 1024, nb = 512 (two diagonal
    blocks of two sub-blocks each), for ONE described v5e chip (ISSUE 46;
    15 s).  Before, each sub-block's column recurrence was a ``while`` of
    XLA ops under ``k<step>/diag`` whose carry the TPU compiler re-laid
    once a column (``copy f32[256,256]``, 256 events a loop, with no name:
    0.138 of the 0.4915 s of ``hplmxp.1x1.b2b``).  On a TPU chip with real
    float32 it is ONE ``tpu_custom_call`` a sub-block, the Pallas kernel
    ``el_lu_nopiv_block`` under the step's ``diag`` scope: the recurrence's
    ``while`` is gone (``triangular_solve``'s own loops, between sub-blocks,
    stay under their own name), and nothing in the program copies a
    sub-block."""
    import elemental_tpu as el
    from elemental_tpu import obs
    from .lapack.test_herm_eig_compiled import square_ops
    n, nb, bs = 1024, 512, NOPIV_BS
    grid = el.Grid([topo.devices[0]])
    A, B = (_abstract(grid, n, k, el.MC, el.MR) for k in (n, 1))
    with obs.metrics_scope() as reg:
        text = jax.jit(lambda a, b: el.mixed_solve(a, b, nb=nb)).lower(
            A, B).compile().as_text()
    assert dict(reg.counters("lu_nopiv_diag")) == {
        ("lu_nopiv_diag", (("impl", "kernel"),)): n // nb}
    lines = [line.strip() for line in text.splitlines()]
    kernels = [line for line in lines if "tpu_custom_call" in line]
    assert len(kernels) == (n // nb) * (nb // bs)
    for line in kernels:
        assert re.match(r"%?el_lu_nopiv_block[.\d]* = ", line), line[:200]
        assert f"f32[{bs},{bs}]" in line.split(" custom-call(")[0]
    steps = [re.search(
        r'op_name="[^"]*/factor/el\.lu_nopiv/k(\d\d)/diag/el_lu_nopiv_block/',
        line).group(1) for line in kernels]
    assert sorted(steps) == sorted(f"{k:02d}" for k in range(n // nb)
                                   for _ in range(nb // bs))
    # the unblocked loop carried the scope's own ``while``; what is left
    # under ``diag`` is named by the op that made it
    under_diag = [line for line in lines
                  if re.search(r'/el\.lu_nopiv/k\d\d/diag[/"]', line)]
    assert under_diag
    assert not [line for line in under_diag
                if re.search(r'/diag/while[/"]', line)]
    whiles = [line for line in under_diag if " while(" in line]
    assert all(re.search(r'/diag/triangular_solve"', line)
               for line in whiles), whiles[:2]
    assert not square_ops([(None, line) for line in lines], bs, ("copy",))


def test_grid_mixed_solve_keeps_the_xla_loop(grid22):
    """The same program on the described 2x2 (n = 1024, nb = 256; 10 s): the
    rule has a one-chip clause (``mixed._diag_blocks_in_vmem``), so the grid
    loop, which no cell or chip run has timed, lowers as it did: no
    ``tpu_custom_call``, every diagonal block ticked ``xla``.  (A Mosaic
    kernel is not partitioned automatically: handed the panel's replicated
    block under GSPMD the lowering raises "cannot be automatically
    partitioned"; a launch there wants a ``shard_map`` of its own.)"""
    import elemental_tpu as el
    from elemental_tpu import obs
    n, nb = 1024, 256
    A, B = (_abstract(grid22, n, k, el.MC, el.MR) for k in (n, 8))
    with obs.metrics_scope() as reg:
        text = jax.jit(lambda a, b: el.mixed_solve(a, b, nb=nb)).lower(
            A, B).compile().as_text()
    assert dict(reg.counters("lu_nopiv_diag")) == {
        ("lu_nopiv_diag", (("impl", "xla"),)): n // nb}
    assert "tpu_custom_call" not in text


def test_divide_and_conquer_hand_off_places_blocks_and_gathers_nothing(topo,
                                                                      one_chip):
    """``tridiag_eig`` with vectors at n = 1024 (ISSUE 42; 15 s), the
    divide and conquer of the program above on its own.  Between the
    replicated levels and the distributed ones the two 512-blocks of
    eigenvectors are PLACED on the diagonal of the [MC,MR] matrix, under
    ``k03/fill``: laid out by a function of (i, j), the TPU compiler made
    one gather over all n^2 entries (``fusion.38 f32[268435456]`` at
    n = 16384: 6.1 of ``heig.1x1.b2b``'s 15.8 s, for 33.5 MB of dense
    copies).  No gather, on its own or inside a fusion, has n^2 entries or
    more, and the hand-off's ops still carry the scope.  (Not the whole
    ``herm_eig``: two tests of one file may go to two workers, and each
    would compile it.)"""
    import elemental_tpu as el
    from elemental_tpu.lapack.tridiag_eig import tridiag_eig
    from .lapack.test_herm_eig_compiled import big_gathers
    n = 1024
    grid = el.Grid([topo.devices[0]])
    d = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
    e = jax.ShapeDtypeStruct((n - 1,), jnp.float32, sharding=one_chip)
    # float32 throughout, as on the chip (the tests' x64 would put the
    # secular stage in float64, which the TPU emulates: 90 s of compile)
    with jax.enable_x64(False):
        text = jax.jit(lambda d, e: tridiag_eig(d, e, grid=grid)).lower(
            d, e).compile().as_text()
    assert not big_gathers(text, n * n)
    assert re.search(r'op_name="[^"]*/el\.tridiag_eig/[^"]*k03/fill/', text)


def test_svd_is_one_program_for_the_described_chip(topo):
    """The whole donated ``jit(el.svd)`` at n = 256 with an explicit block
    of 64, for ONE described v5e chip (ISSUE 53; half a minute; the cell's
    size is ``python -m perf.program_size svd --n 16384``'s).  ``polar``
    read its scale on the host before the first step, so ``jit(svd)``
    raised at trace time.  It lowers and compiles as one program with
    nothing for the host to do: no callback, no infeed or outfeed, no
    send or receive; the static schedule's 2 QR-based and 4
    Cholesky-based steps all in it, each variant's as one loop body under
    its own segment, the QR-based ones over their stack's structure; and the
    inner eigensolve is the one-chip cell's (the one-pass triangle kernel
    under ``el.svd/el.herm_eig``)."""
    import elemental_tpu as el
    from elemental_tpu import obs
    n, nb = 256, 64
    grid = el.Grid([topo.devices[0]])
    A = _abstract(grid, n, n, el.MC, el.MR)
    with obs.metrics_scope() as reg:
        text = jax.jit(lambda a: el.svd(a, nb=nb),
                       donate_argnums=0).lower(A).compile().as_text()
    assert reg.counter_value("svd_route", approach="polar") == 1
    assert reg.counter_value("qdwh_step", kind="qr") == 2
    assert reg.counter_value("qdwh_step", kind="chol") == 4
    assert dict(reg.counters("qdwh_stack_qr")) == {
        ("qdwh_stack_qr", (("route", "structured"),)): 2}
    assert dict(reg.counters("herm_tridiag_hemv")) == {
        ("herm_tridiag_hemv", (("impl", "symv"),)): n // nb}
    assert not re.search(r"callback|infeed|outfeed| send\(| recv\(", text)
    steps = set(re.findall(
        r"/el\.svd/el\.polar/while/body/closed_call/(qdwh_\w+)/", text))
    assert steps == {"qdwh_qr01_02", "qdwh_chol03_06"}
    # ISSUE 54: the QR-based step's products run over the stack's non-zero
    # rows (n + nb of them for every panel, never 2n - s), the thin Q's
    # under a scope of their own, and no (2n x n) identity is made
    products = {}
    for line in text.splitlines():
        found = re.search(
            r'= f32\[(\d+),\d+\]\S* (?:convolution|dot)\(.*op_name="[^"]*'
            r'/qdwh_qr01_02/el\.(qr|thin_q)/k\d+/(?:update|apply)/', line)
        if found:
            products.setdefault(found.group(2), set()).add(int(found.group(1)))
    assert products == {"qr": {nb, n + nb}, "thin_q": {nb, n + nb}}
    assert not re.search(rf"pred\[{2 * n},{n}\]", text)
    kernels = [line for line in text.splitlines()
               if "tpu_custom_call" in line]
    assert kernels and all(
        re.search(r'op_name="[^"]*/el\.svd/el\.herm_eig/'
                  r'el\.hermitian_tridiag/[^"]*/hemv/el_symv_lower/', line)
        for line in kernels)
