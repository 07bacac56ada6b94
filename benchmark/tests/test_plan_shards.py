"""``plan_shards``: the plan over the local shard of A, from the run's
facts alone."""
import bench_copy
import run as harness


def _read(facts):
    reader = harness.load_module(bench_copy.BENCH, "layer_metrics",
                                 "plan_shards")
    return reader.read(None, {"facts": facts})


def test_plan_over_the_local_shard():
    # hpd32k.2x2.b2b and hpd32k.1x1.b2b as the ledger has them (PR 34)
    assert round(_read({"plan_bytes": 5854203904, "n": 32768, "chips": 4}),
                 2) == 5.45
    assert round(_read({"plan_bytes": 9171213312, "n": 32768, "chips": 1}),
                 2) == 2.14


def test_nothing_to_read_without_a_plan():
    assert _read({"chips": 1, "hlo_lines": 1234}) is None
