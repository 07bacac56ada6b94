"""perf.redist_bench smoke (ISSUE 12 satellite): the chain-vs-direct
microbench emits well-formed ``redist_bench/v1`` rows with the bit-match
cross-check green."""
import json

import pytest


def test_run_pair_rows_and_match(grid24):
    from perf.redist_bench import run_pair, _dist_pair
    rows = run_pair(grid24, 24, _dist_pair("MC,MR"), _dist_pair("MR,STAR"),
                    ("chain", "direct"), reps=1, check=True)
    assert [r["path"] for r in rows] == ["chain", "direct"]
    for row in rows:
        assert row["schema"] == "redist_bench/v1"
        assert row["pair"] == "[MC,MR]->[MR,STAR]"
        assert row["match"] is True
        assert row["seconds"] > 0 and row["model_bytes"] >= 0
        json.dumps(row)                      # one JSON line per row
    chain, direct = rows
    assert chain["rounds"] >= direct["rounds"]
    assert direct["plan"] in ("a2a", "ppermute", "local")


def test_cli_smoke_exits_zero(capsys):
    """``--smoke`` is the tools/check.sh gate: tiny 1x1 matrix, every
    row parses, exit 0."""
    from perf import redist_bench
    assert redist_bench.main(["--smoke", "--reps", "1"]) == 0
    out = capsys.readouterr().out
    rows = [json.loads(ln) for ln in out.splitlines() if ln.strip()]
    assert rows and all(r["schema"] == "redist_bench/v1" for r in rows)
    assert all(r["match"] for r in rows)


def test_cli_unpack_times_every_form(capsys):
    """``--unpack`` (ISSUE 29): the local unpack alone on one device, a
    2-D block through the engine and through the one transpose it
    replaced, a 1-D block through the engine, each beside a copy."""
    from perf import redist_bench
    assert redist_bench.main(["--unpack", "--reps", "1", "--blocks",
                              "2x2x8x128;4x5x7:0;2x8x128:1"]) == 0
    rows = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert all(r["schema"] == "redist_unpack_bench/v1" for r in rows)
    assert [(r["block"], r["form"]) for r in rows] == [
        ("2x2x8x128", "copy"), ("2x2x8x128", "engine"),
        ("2x2x8x128", "one_transpose"), ("4x5x7:0", "copy"),
        ("4x5x7:0", "engine"), ("2x8x128:1", "copy"),
        ("2x8x128:1", "engine")]
    assert all(r["ms"] > 0 and r["x_copy"] > 0 for r in rows)


def test_cli_filter_times_every_form(capsys):
    """``--filter`` (ISSUE 32): the local cyclic slice alone (rows, lanes)
    and composed behind a lane interleave as one jitted function, the
    engine's form and the forms it does not use, each checked equal to the
    engine's bit for bit, beside a copy and an elementwise pass."""
    from perf import redist_bench
    assert redist_bench.main(["--filter", "--reps", "1", "--blocks",
                              "2x16x128:0;4x8x12:1;2x8x128:1>0"]) == 0
    rows = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert all(r["schema"] == "redist_filter_bench/v1" for r in rows)
    common = ["copy", "elementwise", "engine", "reshape_index", "strided",
              "switch"]
    assert [(r["block"], r["form"]) for r in rows] == (
        [("2x16x128:0", f) for f in common + ["lane_slice"]]
        + [("4x8x12:1", f) for f in common]
        + [("2x8x128:1>0", f) for f in common + ["lane_slice"]])
    assert all(r["ms"] > 0 and r["x_copy"] > 0 for r in rows)


@pytest.mark.parametrize("spec", ["2x16x128", "2x16x128:2", "0x16x128:0",
                                  "2x16:0"])
def test_cli_filter_refuses_a_bad_block(spec):
    from perf import redist_bench
    with pytest.raises(SystemExit, match="bad block"):
        redist_bench.main(["--filter", "--blocks", spec])
