"""``redist_parts.py`` on hand-made HLO text and events: seconds by part,
the fusion rule, ``planned`` as the remainder, the four shares summing to
``scopes``' ``redist``, the bytes an op writes from an array and from a
tuple shape, an op in a ``while`` counted once an event, the four readers
silent on one chip, without scopes and without parts; and the shapes of a
CPU-compiled program read whole."""
import json

import jax
import numpy as np
import pytest

import bench_copy
import redist_parts
import run as harness
import scopes
import xplane

P = "jit(bench_solve)/jit(main)/el.hpd_solve/factor/el.cholesky/"
V = "k03/panel/el.redist.MC_MR.to.VC_STAR/jit(_redistribute_jit)/shard_map/"
S = "k03/spread/el.redist.panel_spread/jit(_panel_spread_jit)/shard_map/"
ROWS = "k03/swap/el.redist.row_permute/"
READERS = ("redist_pack_share", "redist_wire_share", "redist_unpack_share",
           "redist_relayout_gbps")

#: exchanges in miniature: a hop with its three parts (the pack a fusion
#: named by its own path, the wire an async pair, the unpack a fusion named
#: only by its root), a panel spread whose unpack is a fusion named by the
#: MAJORITY of its members and runs inside a while, a decode nested under
#: both ``unpack`` and ``pack`` (the first after the name counts), the
#: compiler's row motion (no part), a matmul and a compiler's copy
HLO = f"""HloModule jit_bench_solve, is_scheduled=true

%packed (p: f32[8,8]) -> f32[16,8] {{
  %p = f32[8,8]{{1,0}} parameter(0)
  ROOT %pad.1 = f32[16,8]{{1,0}} pad(%p), metadata={{op_name="{P}{V}unpack/pad"}}
}}

%rooted (p.1: f32[8,8]) -> f32[8,8] {{
  %p.1 = f32[8,8]{{1,0}} parameter(0)
  %neg.1 = f32[8,8]{{1,0}} negate(%p.1), metadata={{op_name="{P}{V}pack/neg"}}
  ROOT %copy.1 = f32[8,8]{{1,0:T(8,128)}} copy(%neg.1), metadata={{op_name="{P}{V}unpack/transpose"}}
}}

%voted (p.2: f32[8,8]) -> (f32[8,8], bf16[4,8]) {{
  %p.2 = f32[8,8]{{1,0}} parameter(0)
  %a.1 = f32[8,8]{{1,0}} negate(%p.2), metadata={{op_name="{P}{S}unpack/neg"}}
  %a.2 = f32[8,8]{{1,0}} negate(%a.1), metadata={{op_name="{P}{S}unpack/neg"}}
  %a.3 = bf16[4,8]{{1,0}} convert(%a.2), metadata={{op_name="{P}{S}pack/convert"}}
  ROOT %tuple.9 = (f32[8,8]{{1,0}}, bf16[4,8]{{1,0}}) tuple(%a.2, %a.3)
}}

%body (t: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {{
  %t = (s32[], f32[8,8]{{1,0}}) parameter(0)
  %fusion.3 = (f32[8,8]{{1,0}}, bf16[4,8]{{1,0}}) fusion(%t), kind=kLoop, calls=%voted
  ROOT %tuple.1 = (s32[], f32[8,8]{{1,0}}) tuple(%t, %fusion.3)
}}

ENTRY %main.9 (A: f32[8,8]) -> f32[8,8] {{
  %A = f32[8,8]{{1,0}} parameter(0), metadata={{op_name="A.local"}}
  %fusion.1 = f32[16,8]{{1,0}} fusion(%A), kind=kLoop, calls=%packed, metadata={{op_name="{P}{V}pack/pad"}}
  %all-to-all-start.1 = (f32[16,8]{{1,0}}, f32[16,8]{{1,0}}) all-to-all-start(%fusion.1), metadata={{op_name="{P}{V}wire/all_to_all"}}
  %all-to-all-done.1 = f32[16,8]{{1,0}} all-to-all-done(%all-to-all-start.1), metadata={{op_name="{P}{V}wire/all_to_all"}}
  %fusion.2 = f32[8,8]{{1,0:T(8,128)}} fusion(%all-to-all-done.1), kind=kLoop, calls=%rooted
  %all-gather.1 = s8[4,8,8]{{2,1,0}} all-gather(%A), metadata={{op_name="{P}{S}wire/all_gather"}}
  %while.1 = (s32[], f32[8,8]{{1,0}}) while(%A), condition=%cond, body=%body, metadata={{op_name="{P}{S}while"}}
  %convert.1 = f32[8,8]{{1,0}} convert(%all-gather.1), metadata={{op_name="{P}{S}unpack/vmap(pack)/pack/convert"}}
  %gather.1 = f32[8,8]{{1,0}} gather(%A, %A), metadata={{op_name="{P}{ROWS}gather"}}
  %dot.1 = f32[8,8]{{1,0}} dot(%A, %A), metadata={{op_name="{P}k03/update/dot_general"}}
  ROOT %copy.7 = f32[8,8]{{1,0}} copy(%dot.1)
}}
"""

#: instruction -> ns a run of it; the while body's fusion runs twice a solve
DURATIONS = (("fusion.1", 10), ("all-to-all-start.1", 2),
             ("all-to-all-done.1", 18), ("fusion.2", 30),
             ("all-gather.1", 16), ("fusion.3", 12), ("fusion.3", 12),
             ("convert.1", 6), ("gather.1", 24), ("dot.1", 50),
             ("copy.7", 20))
BUSY = 200.0


def hand_made_trace(devices=2, solves=2):
    """``DURATIONS`` back to back, the two runs of the loop's fusion INSIDE
    a ``while`` event that covers them and 4 ns more of its own."""
    planes = {}
    for device in range(devices):
        ops, modules, t = [], [], 1000.0
        for _ in range(solves):
            start = t
            for name, dur in DURATIONS:
                if name == "fusion.3" and ops[-1][0].split()[0] != name:
                    ops.append(("while.1 (s32[]", t, 28.0))
                    t += 4.0                 # the loop's own time
                ops.append((f"{name} f32[8,8]", t, float(dur)))
                t += dur
            modules.append(("jit_bench_solve(1)", start, t - start))
            t += 500.0
        planes[f"/device:TPU:{device}"] = {"XLA Ops": ops,
                                          "XLA Modules": modules}
    return xplane.reduce_trace(planes, "jit_bench_solve")


def run_of(chips=4, operator="hpd_solve"):
    return {"facts": {"operator": operator, "chips": chips,
                      "solve_module": "jit_bench_solve"}}


def readers(names=READERS + ("redist_share",)):
    return {name: harness.load_module(bench_copy.BENCH, "layer_metrics",
                                      name) for name in names}


def test_part_of_takes_the_first_part_after_the_first_name():
    hop = "el.redist.MC_MR.to.VC_STAR"
    assert redist_parts.part_of(P + V + "wire/all_to_all") == (hop, "wire")
    assert redist_parts.part_of(P + V + "unpack/vmap(pack)/pack/x") == (
        hop, "unpack")
    # a part's name BEFORE the exchange's is a phase's, not a part
    assert redist_parts.part_of("jit(f)/pack/" + V + "transpose") == (
        hop, "planned")
    assert redist_parts.part_of(P + ROWS + "gather") == (
        "el.redist.row_permute", "planned")
    # nested exchanges: the FIRST name's parts
    assert redist_parts.part_of(
        "jit(f)/el.redist.A.to.B/unpack/el.redist.C.to.D/wire/x") == (
        "el.redist.A.to.B", "unpack")


def test_fusion_takes_its_own_part_else_its_roots_else_the_majoritys():
    module = scopes.Module(HLO)
    part = lambda name: redist_parts.redist_part(module, name)
    assert part("fusion.1") == ("el.redist.MC_MR.to.VC_STAR", "pack")  # own
    assert part("fusion.2") == ("el.redist.MC_MR.to.VC_STAR", "unpack")
    assert part("fusion.3") == ("el.redist.panel_spread", "unpack")  # 2 to 1
    assert part("gather.1") == ("el.redist.row_permute", "planned")
    assert part("dot.1") is None and part("copy.7") is None


@pytest.mark.parametrize("shape,want", [
    ("f32[8,8]{1,0}", 256),
    ("f32[30720,30720]{0,1:T(8,128)}", 4 * 30720 * 30720),
    ("bf16[4,8]{1,0:T(8,128)(2,1)}", 64),
    ("s8[4,8,8]{2,1,0}", 256),
    ("pred[16]{0}", 16),
    ("f32[]", 4),
    ("(f32[8,8]{1,0}, bf16[4,8]{1,0})", 256 + 64),
    ("((f32[2]{0}, s32[]), u32[3]{0:S(1)}, token[])", 8 + 4 + 12),
    ("c64[4]{0}", 32),
    ("f8e4m3fn[128]{0}", 128),
])
def test_shape_bytes_of_an_array_and_of_a_tuple(shape, want):
    assert redist_parts.shape_bytes(shape) == want


def test_written_bytes_by_instruction_and_nothing_for_a_start():
    written = redist_parts.written_bytes(HLO)
    assert written["fusion.1"] == 16 * 8 * 4
    assert written["fusion.3"] == 256 + 64            # a tuple: the sum
    assert written["all-to-all-start.1"] == 0         # its done writes it
    assert written["all-to-all-done.1"] == 16 * 8 * 4
    assert written["all-gather.1"] == 256
    assert written.keys() == scopes.Module(HLO).paths.keys()


def test_parts_split_redist_share_and_planned_is_the_remainder(monkeypatch,
                                                               capsys):
    monkeypatch.setattr(scopes, "module_texts", lambda name: [HLO])
    trace, run = hand_made_trace(), run_of()
    busy = trace["devices"][0]["timed_busy_s"] / 2
    assert busy == pytest.approx(BUSY * 1e-9 + 4e-9)
    got = {name: r.read(trace, run) for name, r in readers().items()}
    share = lambda ns: 100.0 * ns * 1e-9 / busy
    assert got["redist_pack_share"] == pytest.approx(share(10))
    # an async pair's start and done both
    assert got["redist_wire_share"] == pytest.approx(share(2 + 18 + 16))
    # the rooted fusion, the loop's fusion TWICE a solve, the decode; the
    # while's own 4 ns under the spread's name but under no part: planned
    assert got["redist_unpack_share"] == pytest.approx(share(30 + 24 + 6))
    result = redist_parts.summary(trace, run)
    assert result["share"]["planned"] == pytest.approx(share(24 + 4))
    assert sum(result["share"].values()) == pytest.approx(
        got["redist_share"])
    assert result["seconds"] == pytest.approx(
        {"pack": 10e-9, "wire": 36e-9, "unpack": 60e-9, "planned": 28e-9})
    detail = scopes.summary(trace, run)["seconds"]
    assert sum(result["seconds"].values()) == pytest.approx(
        sum(s for name, s in detail.items() if name.startswith("el.redist.")))
    # GB the pack and unpack ops write a solve over their seconds: the pad
    # 512, the rooted copy 256, the loop's tuple 320 an EVENT, the decode 256
    wrote = 512 + 256 + 2 * 320 + 256
    assert got["redist_relayout_gbps"] == pytest.approx(
        wrote * 1e-9 / 70e-9)
    line = next(json.loads(text) for text in
                capsys.readouterr().out.splitlines()
                if '"redist_parts"' in text)
    assert line["seconds"] == pytest.approx(result["seconds"])
    assert list(line["by_name"])[0] == "el.redist.MC_MR.to.VC_STAR/unpack"
    assert line["by_name"]["el.redist.panel_spread/unpack"] == pytest.approx(
        [30e-9, 896e-9, 896 / 30])
    assert line["by_name"]["el.redist.row_permute/planned"][0] == \
        pytest.approx(24e-9)
    assert line["by_name"]["el.redist.MC_MR.to.VC_STAR/wire"] == \
        pytest.approx([20e-9, 512e-9, 512 / 20])
    seconds = [v[0] for v in line["by_name"].values()]
    assert seconds == sorted(seconds, reverse=True)
    # one traced window a process: read again, printed once
    assert redist_parts.summary(trace, run) is result
    assert '"redist_parts"' not in capsys.readouterr().out


def test_readers_are_silent_on_one_chip_without_scopes_and_without_parts(
        monkeypatch):
    trace = hand_made_trace()
    monkeypatch.setattr(scopes, "module_texts", lambda name: [HLO])
    for reader in readers(READERS).values():
        assert reader.read(trace, run_of(chips=1)) is None
    # a program that names no scope at all
    bare = "\n".join(line.split(", metadata=")[0] for line in HLO.split("\n"))
    monkeypatch.setattr(scopes, "module_texts", lambda name: [bare])
    trace = hand_made_trace()
    for reader in readers(READERS).values():
        assert reader.read(trace, run_of()) is None
    # the parent's program: el.redist. names and no part under them
    before = HLO.replace("unpack/vmap(pack)/pack/", "")
    for part in redist_parts.PARTS:
        before = before.replace(f"shard_map/{part}/", "shard_map/")
    assert "el.redist." in before
    monkeypatch.setattr(scopes, "module_texts", lambda name: [before])
    trace = hand_made_trace()
    got = {name: r.read(trace, run_of()) for name, r in readers().items()}
    assert got["redist_share"] > 0
    assert all(got[name] is None for name in READERS)


def test_every_entry_names_its_reader_and_the_four_chip_cells():
    with open(bench_copy.BENCH + "/../BENCHMARK.json") as f:
        bench = json.load(f)
    four = [c["name"] for c in bench["workloads"] if c["chips"] == 4]
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name, reader in readers(READERS).items():
        entry = entries[name]
        assert entry["workloads"] == four and len(four) == 5
        assert (entry["layer"], entry["unit"], entry["moves"]) == (
            reader.LAYER, reader.UNIT, reader.MOVES)
        assert entry["source"] == "device_trace"
    assert [m["name"] for m in bench["per_layer"]][-4:] == list(READERS)


def test_shapes_of_a_compiled_program_are_read_whole():
    """Every instruction of a CPU-compiled 2x2 exchange has its bytes, and
    the tuple the backend makes of an all-to-all is the sum of its blocks."""
    import elemental_tpu as el
    grid = el.Grid(list(jax.devices()[:4]))
    A = el.from_global(np.ones((64, 32), np.float32), el.MC, el.MR, grid=grid)
    text = jax.jit(lambda a: el.redistribute(a, el.VC, el.STAR)).lower(
        A).compile().as_text()
    written = redist_parts.written_bytes(text)
    module = scopes.Module(text)
    assert written.keys() == module.paths.keys()
    parts = {redist_parts.part_of(path)[1] for path in module.paths.values()
             if "el.redist." in path}
    assert parts >= {"wire", "unpack"}
    (wire,) = [n for n, p in module.paths.items()
               if p.endswith("wire/all_to_all") and n.startswith("all-to-all")]
    assert written[wire] == 16 * 32 * 4       # this chip's block, both halves
