"""``scopes.py``: classification and self time on hand-made input, and
the lookup of the timed module on a CPU-compiled N = 256 solve."""
import jax
import pytest

import bench_copy
import run as harness
import scopes
import xplane

P = "jit(bench_solve)/jit(main)/"

#: a module in miniature: a while whose body holds a panel fusion, an
#: update fusion with an unnamed root, a collective under a redist scope,
#: a sweep, an op in a driver scope outside any phase, a compiler's copy
HLO = f"""HloModule jit_bench_solve, is_scheduled=true

FileNames
1 "x.py"

%fused_panel (p: f32[8,8]) -> f32[8,8] {{
  %p = f32[8,8]{{1,0}} parameter(0)
  ROOT %sqrt.1 = f32[8,8]{{1,0}} sqrt(%p), metadata={{op_name="{P}el.hpd_solve/factor/el.cholesky/k03/panel/sqrt" stack_frame_id=5}}
}}

%fused_update (p.1: f32[8,8]) -> f32[8,8] {{
  %p.1 = f32[8,8]{{1,0}} parameter(0)
  %dot.1 = f32[8,8]{{1,0}} dot(%p.1, %p.1), metadata={{op_name="{P}el.hpd_solve/factor/el.cholesky/k03/update/dot_general"}}
  %sub.1 = f32[8,8]{{1,0}} subtract(%p.1, %dot.1), metadata={{op_name="{P}el.hpd_solve/factor/el.cholesky/k03/update/sub"}}
  %neg.1 = f32[8,8]{{1,0}} negate(%sub.1), metadata={{op_name="{P}el.hpd_solve/factor/el.cholesky/k04/diag/neg"}}
  ROOT %bitcast.1 = f32[8,8]{{1,0}} bitcast(%neg.1)
}}

%body (t: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {{
  %t = (s32[], f32[8,8]{{1,0}}) parameter(0)
  %fusion.1 = f32[8,8]{{1,0}} fusion(%t), kind=kLoop, calls=%fused_panel, metadata={{op_name="{P}el.hpd_solve/factor/el.cholesky/k03/panel/sqrt"}}
  ROOT %tuple.1 = (s32[], f32[8,8]{{1,0}}) tuple(%t, %fusion.1)
}}

ENTRY %main.9 (A: f32[8,8]) -> f32[8,8] {{
  %A = f32[8,8]{{1,0}} parameter(0), metadata={{op_name="A.local"}}
  %while.1 = (s32[], f32[8,8]{{1,0}}) while(%A), condition=%cond, body=%body, metadata={{op_name="{P}el.hpd_solve/factor/el.cholesky/k03/panel/while"}}
  %fusion.2 = f32[8,8]{{1,0}} fusion(%A), kind=kOutput, calls=%fused_update
  %all-gather-start.1 = f32[8,8]{{1,0}} all-gather-start(%A), metadata={{op_name="{P}el.hpd_solve/factor/el.cholesky/k03/diag/el.redist.MC_MR.to.STAR_STAR/shmap/all_gather"}}
  %triangular-solve.1 = f32[8,8]{{1,0}} triangular-solve(%A, %A), metadata={{op_name="{P}el.hpd_solve/sweeps/el.trsm/k00/solve/triangular_solve"}}
  %select.1 = f32[8,8]{{1,0}} select(%A, %A, %A), metadata={{op_name="{P}el.hpd_solve/factor/el.cholesky/jit(_where)/select_n"}}
  ROOT %copy.7 = f32[8,8]{{1,0}} copy(%select.1)
}}
"""


@pytest.mark.parametrize("path,want", [
    (P + "el.hpd_solve/factor/el.cholesky/k03/update/dot_general",
     ("update", "cholesky/update")),
    (P + "el.lu_solve/factor/el.lu/k12/swap/gather", ("swap", "lu/swap")),
    (P + "el.cholesky/k100/tail/k00/diag/cholesky", ("tail", "cholesky/tail")),
    (P + "el.cholesky/k03/panel/el.redist.MC_MR.to.VC_STAR/shmap/all_to_all",
     ("redist", "el.redist.MC_MR.to.VC_STAR")),
    (P + "el.lu_solve/sweeps/el.redist.row_permute/gather",
     ("redist", "el.redist.row_permute")),
    (P + "el.hpd_solve/sweeps/el.trsm/k02/update/dot_general",
     ("sweep", "trsm/update")),
    (P + "el.hpd_solve/factor/el.cholesky/jit(_where)/select_n",
     ("other", "cholesky/-")),
    (P + "el.hpd_solve/add", ("other", "hpd_solve/-")),
    (P + "k03/update/dot_general", ("unscoped", "unscoped")),
    ("A.local", ("unscoped", "unscoped")),
    ("", ("unscoped", "unscoped")),
])
def test_classify(path, want):
    assert scopes.classify(path) == want


def test_instruction_class_and_fusion_fallbacks():
    module = scopes.Module(HLO)
    assert module.scoped
    cls = module.instruction_class
    assert cls("fusion.1") == ("panel", "cholesky/panel")     # its own
    # no name of its own and an unnamed root: the class most of its fused
    # instructions carry (two update, one diag)
    assert cls("fusion.2") == ("update", "cholesky/update")
    assert cls("all-gather-start.1")[0] == "redist"
    assert cls("triangular-solve.1") == ("sweep", "trsm/solve")
    assert cls("select.1") == ("other", "cholesky/-")
    assert cls("copy.7") == ("unscoped", "unscoped")
    assert cls("while.1")[0] == "panel"
    assert "fusion.3" not in module and "sqrt.1" in module


def test_fusion_takes_its_roots_class_before_the_majority():
    text = HLO.replace("ROOT %bitcast.1 = f32[8,8]{1,0} bitcast(%neg.1)",
                       'ROOT %bitcast.1 = f32[8,8]{1,0} bitcast(%neg.1), '
                       f'metadata={{op_name="{P}el.cholesky/k04/diag/x"}}')
    assert scopes.Module(text).instruction_class("fusion.2") == (
        "diag", "cholesky/diag")


def test_self_time_charges_an_enclosing_event_only_what_is_uncovered():
    events = [("while.1 (s32[], f32[8,8])", 0.0, 100.0),
              ("fusion.1 f32[8,8]", 10.0, 30.0),
              ("while.2 f32[8]", 50.0, 40.0),          # nested while
              ("fusion.1 f32[8,8]", 55.0, 20.0),
              ("copy.7 f32[8,8]", 100.0, 5.0)]
    got = scopes.self_times(events)
    assert got == [("while.1 (s32[], f32[8,8])", 30.0),
                   ("fusion.1 f32[8,8]", 30.0), ("while.2 f32[8]", 20.0),
                   ("fusion.1 f32[8,8]", 20.0), ("copy.7 f32[8,8]", 5.0)]
    assert sum(s for _n, s in got) == xplane.length(xplane.spans(events))


def hand_made_trace():
    ops = [("while.1 (s32[], f32[8,8])", 1000.0, 40.0),
           ("fusion.1 f32[8,8]", 1005.0, 30.0),
           ("fusion.2 f32[8,8]", 1040.0, 30.0),
           ("all-gather-start.1 f32[8,8]", 1070.0, 10.0),
           ("triangular-solve.1 f32[8,8]", 1080.0, 10.0),
           ("select.1 f32[8,8]", 1090.0, 6.0),
           ("copy.7 f32[8,8]", 1096.0, 4.0)]
    modules = [("jit_bench_solve(1)", 1000.0, 100.0)]
    planes = {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": modules}}
    return xplane.reduce_trace(planes, "jit_bench_solve")


def test_summarize_shares_sum_to_the_timed_busy_time():
    got = scopes.summarize(scopes.Module(HLO), hand_made_trace())
    assert got["share"] == pytest.approx({
        "panel": 40.0, "update": 30.0, "redist": 10.0, "sweep": 10.0,
        "other": 6.0, "unscoped": 4.0})
    assert got["sum"] == pytest.approx(100.0)
    assert got["seconds"]["cholesky/panel"] == pytest.approx(40e-9)
    assert got["seconds"]["el.redist.MC_MR.to.STAR_STAR"] == \
        pytest.approx(10e-9)


def test_an_op_missing_from_the_text_raises():
    trace = hand_made_trace()
    trace["devices"][0]["timed_ops"].append(("fusion.77 f32[8]", 1099., 1.))
    with pytest.raises(LookupError, match="fusion.77"):
        scopes.summarize(scopes.Module(HLO), trace)


def test_a_program_without_scopes_reads_as_nothing():
    bare = "\n".join(line.split(", metadata=")[0] + ("}" if line.endswith(
        "{") and "metadata" in line else "") for line in HLO.split("\n"))
    assert not scopes.Module(bare).scoped
    run = {"facts": {"chips": 1}}                       # names no module
    assert scopes.summary(hand_made_trace(), run) is None


@pytest.fixture(scope="module")
def cpu_session(tmp_path_factory):
    bench_dir = bench_copy.make(tmp_path_factory.mktemp("b") / "benchmark")
    _cell, config, traffic = harness.resolve(bench_dir, "t.hpd.2x2")
    kind = harness.load_module(bench_dir, "kinds", config["kind"])
    return kind.setup(config, traffic, jax.devices()[:4], 2147483999)


def entry_events(text):
    """An event of 10 ns for every instruction of the entry computation
    that does work, back to back."""
    _paths, _calls, members = scopes.parse_hlo(text)
    entry = text[text.index("\nENTRY"):].split("(", 1)[0].split()[-1]
    skip = ("parameter", "tuple", "get-tuple-element", "constant", "bitcast")
    names = [n for n, _root in members[entry.lstrip("%")]
             if not n.startswith(skip)]
    return [(f"{n} f32[1]", 1000.0 + 10.0 * i, 10.0)
            for i, n in enumerate(names)]


def test_lookup_finds_the_module_compiles_nothing_and_sums(cpu_session,
                                                           capsys):
    compiles = harness.CompileCounter()
    name = cpu_session.facts["solve_module"]
    texts = scopes.module_texts(name)
    assert texts and all(t.startswith(f"HloModule {name}") for t in texts)
    assert scopes.module_texts("jit_no_such_program") == []
    own = cpu_session._solve.as_text()      # other tests' sessions may live
    assert own in texts
    ops = entry_events(own)
    window = [(f"{name}(1)", 1000.0, 10.0 * len(ops))]
    trace = xplane.reduce_trace(
        {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": window}}, name)
    run = {"facts": cpu_session.facts}
    got = scopes.summary(trace, run)
    assert compiles.requests == 0
    busy = trace["devices"][0]["timed_busy_s"]
    assert sum(got["seconds"].values()) == pytest.approx(busy)
    assert got["sum"] == pytest.approx(100.0)
    for cls in ("diag", "panel", "update", "tail", "redist", "sweep"):
        assert got["share"].get(cls, 0.0) > 0.0, cls
    assert scopes.summary(trace, run) is got            # cached
    printed = capsys.readouterr().out.strip().splitlines()
    assert len(printed) == 1 and "cholesky/update" in printed[0]
    # the readers, through the harness's own loader
    metrics = harness.read_metrics(bench_copy.BENCH, "layer_metrics",
                                   trace, {**run, "peak": {
                                       "bf16_flops_per_s": 1e12}})
    assert {"panel_share", "update_share", "sweep_share", "redist_share",
            "unscoped_share"} <= set(metrics)
    assert "swap_share" not in metrics
    assert compiles.requests == 0
