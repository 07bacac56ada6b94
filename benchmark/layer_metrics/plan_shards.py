"""The planned bytes per device (``facts.plan_bytes``, what ``plan_gb``
reads) over the bytes of A's local shard, ``4 n^2 / chips`` in float32:
how many copies of its share of the operand a device must hold to run the
solve.  The number that says how large an N a grid holds: 16 GB of HBM
over ``plan_shards`` is the largest shard.  Nothing to read where the
run states no plan."""
LAYER = "Drivers"
UNIT = "x"
MOVES = "plan_gb"


def read(trace, run):
    del trace
    facts = run["facts"]
    if "plan_bytes" not in facts or "n" not in facts:
        return None
    return facts["plan_bytes"] / (4.0 * facts["n"] ** 2 / facts["chips"])
