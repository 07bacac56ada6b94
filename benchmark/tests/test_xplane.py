"""The interval arithmetic on hand-made tuples."""
import pytest

import xplane


def test_union_merges_overlapping_nested_and_touching():
    assert xplane.union([(5, 9), (0, 4), (3, 6), (20, 30), (22, 25),
                         (30, 31)]) == [(0, 9), (20, 31)]
    assert xplane.length([(0, 4), (3, 6), (10, 11)]) == 7


def test_clip_cuts_events_at_window_edges():
    events = [("a", 0.0, 10.0), ("b", 12.0, 2.0), ("c", 18.0, 10.0)]
    assert xplane.clip(events, [(5.0, 20.0)]) == [
        ("a", 5.0, 5.0), ("b", 12.0, 2.0), ("c", 18.0, 2.0)]


def test_share_by_name_prefix_counts_nested_collectives_once():
    # an all-gather-start with its all-gather-done nested inside another
    # collective's span: the union covers 40 of the 100 ns window
    ops = [("all-gather-start.1 f32[8]", 10.0, 30.0),
           ("all-gather-done.1 f32[8]", 20.0, 10.0),
           ("all-reduce.3 f32[8]", 35.0, 15.0),
           ("fusion.7 f32[8]", 50.0, 50.0),
           ("reduce.2 f32[]", 0.0, 5.0)]
    coll = xplane.named(ops, xplane.COLLECTIVE_PREFIXES)
    assert [e[0] for e in coll] == [
        "all-gather-start.1 f32[8]", "all-gather-done.1 f32[8]",
        "all-reduce.3 f32[8]"]
    assert xplane.length(xplane.spans(coll)) == 40.0


def test_top_names_sums_by_name():
    ops = [("a", 0.0, 1e9), ("b", 0.0, 3e9), ("a", 5.0, 2.5e9)]
    assert xplane.top_names(ops, 1) == [["a", 3.5]]
    assert xplane.top_names(ops, 5) == [["a", 3.5], ["b", 3.0]]


def test_gaps_are_labelled_by_the_ops_around_them():
    ops = [("a", 10.0, 10.0), ("inside_a", 12.0, 2.0), ("b", 50.0, 10.0)]
    found = xplane.gaps(ops, [(0.0, 100.0)], 5)
    assert [g[0] for g in found] == ["b -> window end", "a -> b",
                                     "window start -> a"]
    assert [g[1] for g in found] == pytest.approx([40e-9, 30e-9, 10e-9])
    assert len(xplane.gaps(ops, [(0.0, 100.0)], 1)) == 1


def test_short_name_keeps_the_op_and_its_shape():
    assert xplane.short_name(
        "%fusion.3 = f32[30720,30720]{0,1:T(8,128)} fusion(f32[30720,30720]"
        "{0,1:T(8,128)} %fusion.8), kind=kOutput") == "fusion.3 f32[30720,30720]"
    assert xplane.short_name(
        "%all-gather-start.2 = (f32[8,8]{1,0}, f32[16,8]{1,0}) "
        "all-gather-start(%x)") == "all-gather-start.2 f32[8,8]"
    assert xplane.short_name("jit_bench_solve(64)") == "jit_bench_solve(64)"


def test_reduce_trace_takes_only_the_timed_program_s_windows():
    ops = [("gen.1 f32[8]", 0.0, 50.0),
           ("fusion.1 f32[8]", 100.0, 40.0), ("fusion.2 f32[8]", 150.0, 50.0),
           ("check.1 f32[8]", 300.0, 20.0),
           ("fusion.1 f32[8]", 400.0, 100.0)]
    modules = [("jit_generate(5)", 0.0, 50.0),
               ("jit_bench_solve(7)", 100.0, 100.0),
               ("jit_check(9)", 300.0, 20.0),
               ("jit_bench_solve(7)", 400.0, 100.0)]
    planes = {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": modules},
              "/device:TPU:1": {"XLA Ops": ops, "XLA Modules": modules},
              "/host:CPU": {"python3": [("x", 0.0, 1.0)]}}
    trace = xplane.reduce_trace(planes, "jit_bench_solve")
    assert sorted(trace["devices"]) == [0, 1]
    d = trace["devices"][0]
    assert d["n_timed"] == 2
    assert d["timed_s"] == pytest.approx(200e-9)
    assert d["timed_busy_s"] == pytest.approx(190e-9)
    assert d["busy_s"] == pytest.approx(260e-9)
    assert d["window_s"] == pytest.approx(500e-9)


def test_a_trace_without_a_tpu_plane_is_an_error():
    with pytest.raises(RuntimeError, match="no TPU plane"):
        xplane.reduce_trace({"/host:CPU": {"python3": []}}, "jit_x")
