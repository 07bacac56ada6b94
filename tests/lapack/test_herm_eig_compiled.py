"""The Hermitian eigensolve as ONE compiled program (ISSUE 37).

``jit(herm_eig)`` with A donated, on 1x1 and on the 2x2 virtual mesh,
float32, against float64 ``numpy.linalg.eigh`` of the same matrix; the
scopes and counters its three stages carry into the compiled program
(grammar in ``elemental_tpu/obs/__init__.py``), read the way
``benchmark/scopes.py`` reads them on the chip; and the proof that the
names cost the program nothing.  ``dc_min=64, repl_max=64`` put the test
sizes through the divide and conquer, n = 320 through a distributed merge
(four leaves of 80: one level of two replicated merges, then one merge on
the [MC,MR] eigenvector matrix), which the defaults reach only above 512;
n = 398 on 2x2 (ISSUE 50) through TWO distributed levels, a padded tree and
a ragged last panel, and through the pin of what one column of the grid
reduction exchanges.
"""
import collections
import contextlib
import functools
import importlib.util
import math
import os
import re

import jax
import numpy as np
import pytest

import elemental_tpu as el
from elemental_tpu import obs
from ..obs.test_scopes import op_names, stripped

NB = 64
EPS = float(np.finfo(np.float32).eps)
GRIDS = ["1x1", "2x2"]


def _grid(name):
    r, c = (int(d) for d in name.split("x"))
    return el.Grid(list(jax.devices()[:r * c]), height=r)


def _symmetric(n):
    G = np.random.default_rng(37 + n).uniform(-1, 1, size=(n, n))
    return ((G + G.T) / 2).astype(np.float32)


def _bench_scopes():
    """``benchmark/scopes.py``: plain Python, nothing of the program."""
    path = os.path.join(os.path.dirname(__file__), "..", "..", "benchmark",
                        "scopes.py")
    spec = importlib.util.spec_from_file_location("benchmark_scopes", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@contextlib.contextmanager
def _fresh_inner_jits():
    """The two jitted stages rebuilt around NEW function objects for the
    time of the block: jax keys its trace cache by the function, so what is
    traced under the block runs their Python again (names as
    ``jax.named_scope`` is NOW, counters ticking) whatever an earlier test
    left cached, and no cache another test compiled into is cleared."""
    def anew(fn):
        return lambda *args: fn(*args)
    # (the package re-exports the FUNCTION tridiag_eig over its module)
    condense = importlib.import_module("elemental_tpu.lapack.condense")
    tridiag_eig = importlib.import_module("elemental_tpu.lapack.tridiag_eig")
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(condense, "_tridiag_panel", jax.jit(
            anew(condense._tridiag_panel.__wrapped__),
            static_argnums=(2, 3, 4, 5, 6)))
        patch.setattr(tridiag_eig, "_tridiag_eig_jit", jax.jit(
            anew(tridiag_eig._tridiag_eig_jit.__wrapped__),
            static_argnums=(2, 3, 4, 5, 6, 7)))
        yield


def _lower(grid_name, n):
    A = el.from_global(_symmetric(n), el.MC, el.MR, grid=_grid(grid_name))

    def bench_solve(A):
        return el.herm_eig(A, nb=NB, dc_min=64, repl_max=64)
    with _fresh_inner_jits():
        return jax.jit(bench_solve, donate_argnums=0).lower(A).compile()


@functools.lru_cache(maxsize=None)
def compiled(grid_name, n):
    """(executable, counters ticked while it was traced)."""
    with obs.metrics_scope() as reg:
        exe = _lower(grid_name, n)
    counts = {name: {labels: v for (_name, labels), v
                     in reg.counters(name).items()}
              for name in ("herm_tridiag_panel", "herm_tridiag_symmetrize",
                           "herm_tridiag_hemv", "dc_merge", "dc_fill_block",
                           "gemm_route", "apply_q_panel")}
    return exe, counts


# ------------------------------------------------------------- the answer

def _agrees_with_float64_numpy(grid_name, n):
    F = _symmetric(n)
    exe, _ = compiled(grid_name, n)
    A = el.from_global(F, el.MC, el.MR, grid=_grid(grid_name))
    w, Z = exe(A)
    w = np.asarray(w, np.float64)
    Zg = np.asarray(el.to_global(Z), np.float64)
    F64 = F.astype(np.float64)
    want = np.linalg.eigh(F64)[0]
    norm2 = np.abs(want).max()
    # a backward-stable float32 reduction moves each eigenvalue by a small
    # multiple of eps ||A||_2 (Weyl); 50 leaves room for the n reflectors
    assert np.abs(w - want).max() <= 50 * EPS * norm2
    assert np.all(np.diff(w) >= 0), "eigenvalues not ascending"
    # the benchmark's two numbers (benchmark/reference_eig.py).  Both read
    # under 2 eps here (float64 secular stage under the tests' x64); the
    # cell's own limits come from the chip, where that stage is float32
    residual = np.linalg.norm(F64 @ Zg - Zg * w[None, :]) / (
        np.linalg.norm(F64) * np.linalg.norm(Zg))
    orthogonality = np.linalg.norm(Zg.T @ Zg - np.eye(n)) / np.sqrt(n)
    assert residual <= 10 * EPS, residual
    assert orthogonality <= 20 * EPS, orthogonality


@pytest.mark.parametrize("n", [192, 320])
@pytest.mark.parametrize("grid_name", GRIDS)
def test_compiled_herm_eig_agrees_with_float64_numpy(grid_name, n):
    _agrees_with_float64_numpy(grid_name, n)


#: an order whose divide and conquer has two DISTRIBUTED levels on 2x2 and
#: whose reduction ends in a ragged panel (ISSUE 50)
N_DEEP = 398


def test_two_distributed_merge_levels_and_a_ragged_last_panel_on_2x2():
    """What n = 192 (no distributed merge) and n = 320 (ONE, between two
    blocks of 160, every panel 64 wide but the last of 63) could not show:
    at n = 398 the tree is eight leaves of 50 over a PADDED order of 400,
    one replicated level (four merges to 100), the four blocks placed on
    the [MC,MR] matrix's diagonal, then two distributed levels (two merges
    to 200 whose operands are blocks of the matrix, one to 400): a merge
    whose INPUT was a distributed merge's output; the reduction's seventh
    panel is 13 columns wide and every local view (199, 167, ... 7 rows)
    is of odd order.  Against float64 numpy, as the orders above."""
    _exe, counts = compiled("2x2", N_DEEP)
    assert counts["herm_tridiag_panel"] == {(): 7}
    assert (N_DEEP - 1) % NB == 13
    assert counts["dc_merge"] == {(("kind", "replicated"),): 4,
                                  (("kind", "distributed"),): 3}
    assert counts["dc_fill_block"] == {(): 4}
    _agrees_with_float64_numpy("2x2", N_DEEP)


@pytest.mark.parametrize("grid_name", ["2x2", "2x4"])
def test_a_level_of_merges_on_the_grain_is_one_loop(grid_name):
    """n = 398: the level of two merges to 200 (blocks of 100, a multiple
    of both strides on 2x2 and 2x4) is ONE merge compiled in a
    ``fori_loop`` whose counter gives the blocks' offset, so a level costs
    the program one merge whatever its width (31 merges, five bodies at
    n = 16384 on 2x2: the unrolled program's cache entry, 214 MB, was over
    the chip machine's 192 MiB an entry and every run compiled; PERF.md 6,
    PR 51).  The last level's one merge is no loop, and the counters read
    what the device runs: three merges, two products each."""
    exe, counts = compiled(grid_name, N_DEEP)
    names = op_names(exe.as_text())
    k02 = [n for n in names if re.search(r"/el\.tridiag_eig/.*k02/merge/", n)]
    k03 = [n for n in names if re.search(r"/el\.tridiag_eig/.*k03/merge/", n)]
    before = [n.split("/el.tridiag_eig/")[1].split("/k0")[0]
              for n in k02 + k03]
    assert k02 and k03 and (
        [b.endswith("/while/body/closed_call") for b in before]
        == [True] * len(k02) + [False] * len(k03))
    for level in (k02, k03):
        assert any("/el.gemm/" in n for n in level)
    assert counts["dc_merge"][(("kind", "distributed"),)] == 3
    assert counts["gemm_route"] == {(("alg", "slice"),): 6}


def test_one_device_keeps_its_merges_unrolled():
    """The one-chip program is left as it was measured
    (``heig.1x1.b2b``): no merge of its divide and conquer is in a loop."""
    names = op_names(compiled("1x1", N_DEEP)[0].as_text())
    merges = [n for n in names if re.search(r"/el\.tridiag_eig/.*/merge", n)]
    assert merges and not any("/while/body/closed_call/k0" in n
                              for n in merges)


# -------------------------------------------------------------- the names

#: stage -> the phases its ops carry
STAGES = {"hermitian_tridiag": ("hemv", "panel", "update"),
          "tridiag_eig": ("leaf", "secular", "fill", "merge"),
          "apply_q_herm_tridiag": ("apply",)}


@pytest.mark.parametrize("grid_name", GRIDS)
def test_compiled_program_carries_every_scope_and_classifies(grid_name):
    scopes = _bench_scopes()
    names = op_names(compiled(grid_name, 320)[0].as_text())
    found = {scopes.classify(name) for name in names}
    for stage, phases in STAGES.items():
        for phase in phases:
            pattern = re.compile(
                rf"/el\.herm_eig/el\.{stage}/(.*/)?k\d\d+/{phase}(/|$)")
            assert any(pattern.search(n) for n in names), (stage, phase)
            assert (phase, f"{stage}/{phase}") in found, (stage, phase)
    # hemv and panel are siblings in the column loop: no hemv op may have a
    # panel scope before it in its path, or it would be charged to panel
    hemv = [n for n in names if "/hemv" in n]
    assert hemv and not any(re.search(r"k\d\d+/panel/.*hemv", n)
                            for n in hemv)
    # the once-a-panel mirror of the trailing view (ISSUE 38) is the
    # matvec's cost: its ops stand OUTSIDE the column loop, under every
    # panel's own k<panel>/hemv, the final select among them, and read
    # hemv (on a grid the exchange reads its el.redist. name), never
    # unscoped, never panel
    for k in range(5):
        mirror = [n for n in hemv if "_tridiag_panel" not in n and re.search(
            rf"/el\.hermitian_tridiag/k{k:02d}/hemv(/|$)", n)]
        assert any(n.endswith("/hemv/jit(_where)/select_n")
                   for n in mirror), k
        assert {scopes.classify(n)[0] for n in mirror} <= {
            "hemv", scopes.REDIST}, k
    # a distributed merge's gemm nests its own panels under the merge
    assert any(re.search(r"el\.tridiag_eig/.*k02/merge/el\.gemm/k\d\d+/panel",
                         n) for n in names)
    # the replicated level's scopes stand AROUND its vmaps
    assert not any("vmap(k" in n for n in names)
    assert not any(cls == scopes.UNSCOPED and "el." in name
                   for name in names for cls in [scopes.classify(name)[0]])
    if grid_name == "2x2":
        assert (scopes.REDIST, "el.redist.MC_MR.to.STAR_STAR") in found


def test_counters_read_the_panels_and_the_merges():
    """n = 320, nb = 64: five panels each way; four leaves of 80, so one
    level of two replicated merges, the two blocks of 160 placed on the
    [MC,MR] matrix's diagonal, then one distributed merge."""
    for grid_name in GRIDS:
        _exe, counts = compiled(grid_name, 320)
        assert counts["herm_tridiag_panel"] == {(): 5}
        assert counts["herm_tridiag_symmetrize"] == {(): 5}
        assert counts["apply_q_panel"] == {(): 5}
        assert counts["dc_merge"] == {(("kind", "replicated"),): 2,
                                      (("kind", "distributed"),): 1}
        assert counts["dc_fill_block"] == {(): 2}


def test_cpu_backend_and_grids_take_the_mirror_path():
    """ISSUE 44, ISSUE 52: the one-pass triangle ``symv`` kernel is the
    matvec of ONE TPU chip and, each chip on its own shard, of a SQUARE
    grid of TPU chips.  This file compiles for the CPU backend (an
    interpreted kernel a column), which keeps the mirror path on every
    grid, as non-square grids and complex entries do anywhere: every panel
    says ``impl=mirror`` and the program holds no kernel
    (``tests/test_chip_compile.py`` reads the TPU's programs)."""
    for grid_name in GRIDS:
        exe, counts = compiled(grid_name, 320)
        assert counts["herm_tridiag_hemv"] == {(("impl", "mirror"),): 5}
        assert "el_symv_lower" not in exe.as_text()


def test_phases_are_in_the_canonical_list():
    for phases in STAGES.values():
        assert set(phases) <= set(obs.PHASES)


# --------------------------------------- the blocks are placed, not gathered

_MOVE = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]*)\](?:\{[^}]*\})? "
                   r"(gather|scatter)\(([^)]*)\)(.*)$", re.M)
_ARRAY = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = \w+\[([\d,]*)\]", re.M)


def _entries(dims):
    return math.prod(int(d) for d in dims.split(",") if d)


def big_moves(text, least):
    """``[(entries, opcode, op_name)]`` of the ``gather`` and ``scatter``
    instructions of the optimized HLO, on their own or inside a fusion's
    computation, that move ``least`` entries or more: a gather's result,
    a scatter's UPDATES (its last operand; its result is the whole array
    written into, however few entries land there)."""
    arrays = {name: _entries(dims) for name, dims in _ARRAY.findall(text)}
    found = []
    for dims, opcode, operands, rest in _MOVE.findall(text):
        entries = _entries(dims)
        if opcode == "scatter":
            updates = re.findall(r"%?([A-Za-z_][\w.\-]*)\s*(?:,|$)", operands)
            entries = arrays[updates[-1]]
        name = re.search(r'op_name="([^"]*)"', rest)
        if entries >= least:
            found.append((entries, opcode, name.group(1) if name else ""))
    return found


def big_gathers(text, least):
    """``[(entries, op_name)]`` of :func:`big_moves`' gathers."""
    return [(entries, name) for entries, opcode, name
            in big_moves(text, least) if opcode == "gather"]


def test_the_gather_reader_finds_a_fused_gather():
    text = """
%fused_computation.38 (param_0.116: f32[2,160,160], param_1: s32[320,320,3]) -> f32[320,320] {
  ROOT %gather.330 = f32[320,320]{1,0:T(8,128)} gather(%param_0.116, %param_1), offset_dims={}, metadata={op_name="jit(f)/k03/fill/gather" stack_frame_id=260}
}
%fused_computation.39 (param_0.117: f32[4,80,80], param_1.2: s32[316,3], param_2.3: f32[316]) -> f32[4,80,80] {
  %param_0.117 = f32[4,80,80]{2,1,0} parameter(0)
  %param_1.2 = s32[316,3]{1,0} parameter(1)
  %param_2.3 = f32[316]{0} parameter(2)
  ROOT %scatter.7 = f32[4,80,80]{2,1,0} scatter(%param_0.117, %param_1.2, %param_2.3), update_window_dims={}, to_apply=%add, metadata={op_name="jit(f)/k00/leaf/scatter-add"}
}
ENTRY %main {
  %gather.2 = f32[320]{0} gather(%a, %b), offset_dims={}
  %g = f32[102400]{0} gather(%a, %b), offset_dims={}
  %u = f32[160,160]{1,0} fusion(%x), kind=kLoop, calls=%f
  %s = f32[320,320]{1,0} scatter(%z, %i, %u), update_window_dims={}, to_apply=%set, metadata={op_name="jit(f)/el.redist.VC_STAR.to.MC_MR/scatter"}
}"""
    assert big_gathers(text, 320 * 320) == [
        (102400, "jit(f)/k03/fill/gather"), (102400, "")]
    assert [size for size, _ in big_gathers(text, 320)] == [
        102400, 320, 102400]
    # a scatter counts the entries it WRITES: the leaves' 316 couplings,
    # not the 25,600 entries of the batch they are added to
    assert [found for found in big_moves(text, 300)
            if found[1] == "scatter"] == [
        (316, "scatter", "jit(f)/k00/leaf/scatter-add"),
        (25600, "scatter", "jit(f)/el.redist.VC_STAR.to.MC_MR/scatter")]


@pytest.mark.parametrize("grid_name,n", [("1x1", 320), ("2x2", 320),
                                         ("2x2", N_DEEP), ("2x4", N_DEEP)])
def test_hand_off_gathers_no_entry_of_the_eigenvector_matrix(grid_name, n):
    """ISSUE 42: the batch of eigenvector blocks reaches the [MC,MR] matrix
    as dense block copies.  Laid out by a function of (i, j) the compiler
    made ONE gather over all npad^2 entries, 22.7 ns an entry on the chip
    (6.1 of ``heig.1x1.b2b``'s 15.8 s).  ISSUE 51: the distributed merges'
    products moved their operands the same way, through the plan
    executor's index tables (a gather in, a scatter out, 15 ns an entry:
    1.94 of ``heig.2x2.b2b``'s 5.58 s), which this test let pass under
    their ``el.redist.`` name.  Under ANY name, no gather and no scatter of
    the compiled eigensolve moves a device's share of the matrix, n^2 /
    chips entries, or more: every product of a distributed merge (two a
    merge, all of them ``slice`` at these sizes) moves blocks."""
    chips = _grid(grid_name).size
    exe, counts = compiled(grid_name, n)
    assert not big_moves(exe.as_text(), n * n // chips)
    merges = counts["dc_merge"].get((("kind", "distributed"),), 0)
    assert merges == {320: 1, N_DEEP: 3}[n]
    assert counts["gemm_route"] == (
        {(("alg", "slice"),): 2 * merges} if chips > 1 else {})


# ------------------------------------- one read of the trailing view a column

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$")
_CALLED = re.compile(r"(?:calls|body|condition|to_apply)=%?([\w.\-]+)")
_WHILE_BODY = re.compile(r" while\(.*body=%?([\w.\-]+)")


def column_loops(text):
    """``{panel: [(computation, instruction)]}``: for every ``while`` of
    the optimized HLO whose body holds a ``k<panel>/hemv`` op (a column
    loop of the reduction), the instructions of the body and of every
    computation it calls, fusions included."""
    comps, name = {}, None
    for line in text.splitlines():
        line = line.strip()
        found = _COMPUTATION.match(line)
        if found:
            name = found.group(1)
            comps[name] = []
        elif line == "}":
            name = None
        elif name is not None:
            comps[name].append(line)
    loops = {}
    for body in {b for lines in comps.values() for line in lines
                 for b in _WHILE_BODY.findall(line)}:
        seen, todo = [], [body]
        while todo:
            c = todo.pop()
            if c not in seen and c in comps:
                seen.append(c)
                todo.extend(x for line in comps[c]
                            for x in _CALLED.findall(line))
        lines = [(c, line) for c in seen for line in comps[c]]
        panels = {int(k) for _c, line in lines for k in re.findall(
            r"/el\.hermitian_tridiag/[^\"]*?while/body/[^\"]*?k(\d\d+)/hemv",
            line)}
        if panels:
            (k,) = panels
            assert k not in loops, f"two column loops of panel {k}"
            loops[k] = lines
    return loops


def square_ops(lines, nt, opcodes):
    """The instructions among ``lines`` whose RESULT is an nt x nt array
    and whose opcode is one of ``opcodes``."""
    shape = re.compile(rf"^(?:ROOT )?%?[\w.\-]+ = \w+\[{nt},{nt}\](?:\{{[^}}]*\}})? "
                       rf"(?:{'|'.join(opcodes)})\(")
    return [line for _c, line in lines if shape.match(line)]


@pytest.mark.parametrize("grid_name", GRIDS)
def test_column_loop_reads_the_trailing_view_once(grid_name):
    """ISSUE 38: the trailing view is made Hermitian-full once a panel and
    the column loop's matvec is ONE dot against it, the loop's invariant
    operand as it stands: before, two masked dots a column and, on the
    chip, the compiler's relayout of the whole view for the transposed one
    (``tests/test_chip_compile.py`` reads that program; the CPU backend
    assigns layouts otherwise and never made that copy)."""
    n, height = 320, 1 if grid_name == "1x1" else 2
    loops = column_loops(compiled(grid_name, n)[0].as_text())
    assert sorted(loops) == list(range(5))
    for k, lines in loops.items():
        nt = (n - k * NB) // height              # the local view's order
        dots = [line for _c, line in lines
                if re.search(r" dot\(", line) and "/hemv/" in line]
        assert len(dots) == 1, (k, dots)
        assert "operand_precision={highest,highest}" in dots[0]
        # its left operand is the loop's invariant, the full view itself
        lhs = re.search(r" dot\(%?([\w.\-]+),", dots[0]).group(1)
        (made,) = [line for _c, line in lines
                   if re.match(rf"(ROOT )?%?{re.escape(lhs)} = ", line)]
        assert re.search(rf"= f32\[{nt},{nt}\]\S* get-tuple-element\(",
                         made), (k, made)
        if nt > NB:         # (at nt == nb the panel's own blocks are square)
            assert not square_ops(lines, nt, ("copy", "transpose", "select")), k


#: a collective's opcode in a line of optimized HLO (an ``op_name`` spells
#: ``all_to_all`` with underscores, so only the instruction matches)
COLLECTIVE = re.compile(
    r" (all-gather|all-to-all|all-reduce|collective-permute|reduce-scatter)"
    r"(?:-start)?\(")


def test_a_column_of_the_grid_reduction_exchanges_five_times_and_writes_no_shard():
    """ISSUE 50: what ONE column of the reduction costs on a grid ON THE
    MIRROR PATH, pinned in the compiled loop body (the orders above held
    the product to one read of the view; nothing counted the exchanges
    beside it).  Since ISSUE 52 this is the path of the CPU (what this file
    compiles for), of non-square grids and of complex entries; a square
    grid of TPU chips runs one kernel and ONE all-reduce a column
    (``tests/test_chip_compile.py``).  The vector goes to
    ``[MR,STAR]`` in three collectives (an all-to-all, a collective-permute,
    an all-gather), the product's partial sums are joined by the compiler's
    own all-reduce, which carries the product's name and reads ``hemv``, and
    the result is replicated again by one all-gather: FIVE dependent
    collectives a column, 81,920 a solve at n = 16384, each on a vector.  A
    sixth is a regression of the engine's route, not noise.  And nothing in
    the body makes an array of the trailing shard's size: the local view is
    the loop's invariant, read by the one product."""
    loops = column_loops(compiled("2x2", N_DEEP)[0].as_text())
    assert sorted(loops) == list(range(7))
    for k, lines in loops.items():
        found = collections.Counter()
        for _c, line in lines:
            opcode = COLLECTIVE.search(line)
            if opcode:
                name = re.search(r'op_name="([^"]*)"', line).group(1)
                hop = [s for s in name.split("/") if s.startswith("el.redist.")]
                assert f"/k{k:02d}/hemv/" in name, (k, name)
                found[opcode.group(1), hop[0] if hop else "product"] += 1
        assert found == {
            ("all-to-all", "el.redist.MC_MR.to.MR_STAR"): 1,
            ("collective-permute", "el.redist.MC_MR.to.MR_STAR"): 1,
            ("all-gather", "el.redist.MC_MR.to.MR_STAR"): 1,
            ("all-reduce", "product"): 1,
            ("all-gather", "el.redist.MC_MR.to.STAR_STAR"): 1}, (k, found)
        nt = (N_DEEP - k * NB) // 2              # the local view's order
        made = [line for _c, line in lines
                if re.match(rf"(ROOT )?%?[\w.\-]+ = f32\[{nt},{nt}\]", line)
                and " get-tuple-element(" not in line
                and " parameter(" not in line]
        assert not made, (k, made)


# ------------------------------------------------------- what the names cost

@pytest.mark.parametrize("grid_name", GRIDS)
def test_scopes_cost_the_compiled_eigensolve_nothing(grid_name, monkeypatch):
    """With ``jax.named_scope`` a null context the optimized HLO of
    ``herm_eig`` at n = 256 is the same text, metadata apart."""
    with_scopes = _lower(grid_name, 256).as_text()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    without = _lower(grid_name, 256).as_text()
    assert not any("el." in n for n in op_names(without))
    assert any("/el.herm_eig/" in n for n in op_names(with_scopes))
    assert _renumbered(stripped(with_scopes)) == _renumbered(stripped(without))


def _renumbered(text):
    """The HLO text with every ``%name.<n>`` numbered anew, per base name,
    in the order of first appearance.  XLA's suffixes are unique ids handed
    out while it optimizes, and with the names in the metadata two ``mul``s
    of the secular stage draw theirs in another order (``mul.787`` for
    ``mul.503``): the instructions, their order and their operands are the
    same, which is what this keeps."""
    seen, count = {}, {}

    def anew(match):
        name = match.group(0)
        if name not in seen:
            base = re.sub(r"\.\d+$", "", name)
            count[base] = count.get(base, 0) + 1
            seen[name] = f"{base}.{count[base]}"
        return seen[name]
    return re.sub(r"%[\w.\-]+", anew, text)
