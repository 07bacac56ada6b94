"""Unit tests for the recursive jaxpr collective walker (ISSUE 3)."""
import jax
import jax.numpy as jnp
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from elemental_tpu import Grid
from elemental_tpu.analysis import (collect_events, count_pjit_calls,
                                    estimate_bytes,
                                    find_loop_invariant_collectives)
from elemental_tpu.core.compat import shard_map


@pytest.fixture(scope="module")
def g22():
    return Grid(jax.devices()[:4], height=2)


def _smap(g, fn, n_in=1):
    def outer(*args):
        return shard_map(fn, mesh=g.mesh, in_specs=(P(),) * n_in,
                         out_specs=P(), check_vma=False)(*args)
    return outer


def test_psum_event_axes_and_bytes(g22):
    fn = _smap(g22, lambda x: lax.psum(x, ("mc", "mr")))
    closed = jax.make_jaxpr(fn)(jax.ShapeDtypeStruct((8, 8), jnp.float32))
    evs = collect_events(closed)
    assert len(evs) == 1
    ev = evs[0]
    assert ev.prim == "psum" and set(ev.axes) == {"mc", "mr"}
    assert ev.axis_size == 4 and ev.shape == (8, 8)
    assert ev.dtype == "float32" and ev.count == 1 and ev.static
    assert ev.bytes_per_call == estimate_bytes("psum", 8 * 8 * 4, 4)


def test_scan_multiplies_count(g22):
    def body(x):
        def step(c, _):
            return c + lax.psum(c, "mc"), None
        out, _ = lax.scan(step, x, None, length=5)
        return out

    closed = jax.make_jaxpr(_smap(g22, body))(
        jax.ShapeDtypeStruct((4,), jnp.float32))
    evs = collect_events(closed)
    assert len(evs) == 1
    assert evs[0].count == 5 and evs[0].static
    assert any(p.startswith("scan[5]") for p in evs[0].path)


def test_while_marks_non_static(g22):
    def body(x):
        def cond(c):
            return c[0] < 3

        def step(c):
            return (c[0] + 1, c[1] + lax.psum(c[1], "mr"))
        return lax.while_loop(cond, step, (0, x))[1]

    closed = jax.make_jaxpr(_smap(g22, body))(
        jax.ShapeDtypeStruct((4,), jnp.float32))
    evs = collect_events(closed)
    assert len(evs) == 1 and not evs[0].static


def test_jit_inside_jit_count_and_label():
    # the one test that pins how the installed JAX spells a nested
    # jax.jit call: the walker matches the primitive object, counts each
    # call site once, and labels the scope ``jit:<name>`` whatever the
    # primitive is called this release -- a rename fails here, not in
    # every golden
    @jax.jit
    def inner(x):
        return x * 2.0

    @jax.jit
    def outer(x):
        return inner(x) + inner(x + 1.0)

    closed = jax.make_jaxpr(lambda x: outer(x))(
        jax.ShapeDtypeStruct((4,), jnp.float32))
    assert count_pjit_calls(closed, "outer") == 1
    assert count_pjit_calls(closed, "inner") == 2
    from elemental_tpu.analysis.jaxpr_walk import _scope_label
    (eqn,) = closed.jaxpr.eqns
    assert _scope_label(eqn) == "jit:outer"
    assert [_scope_label(e) for e in eqn.params["jaxpr"].jaxpr.eqns
            if "name" in e.params][:1] == ["jit:inner"]


def test_nested_pjit_recursion_and_count(g22):
    @jax.jit
    def inner(x):
        return lax.psum(x, "mc")

    def body(x):
        return inner(x) + inner(x)

    closed = jax.make_jaxpr(_smap(g22, body))(
        jax.ShapeDtypeStruct((4,), jnp.float32))
    evs = collect_events(closed)
    assert [e.prim for e in evs] == ["psum", "psum"]
    assert all("jit:inner" in e.path for e in evs)
    assert count_pjit_calls(closed, "inner") == 2


def test_estimate_bytes_formulas():
    nb = 1000
    assert estimate_bytes("all_gather", nb, 4) == 3000
    assert estimate_bytes("reduce_scatter", nb, 4) == 750
    assert estimate_bytes("psum", nb, 4) == 1500
    assert estimate_bytes("all_to_all", nb, 4) == 750
    assert estimate_bytes("ppermute", nb, 4) == nb
    assert estimate_bytes("all_gather", nb, 1) == 0


def test_loop_invariant_collective_found(g22):
    def body(x, y):
        def step(c, _):
            # psum of the loop-INVARIANT y: hoistable
            return c + lax.psum(y, "mc"), None
        out, _ = lax.scan(step, x, None, length=3)
        return out

    closed = jax.make_jaxpr(_smap(g22, body, n_in=2))(
        jax.ShapeDtypeStruct((4,), jnp.float32),
        jax.ShapeDtypeStruct((4,), jnp.float32))
    found = find_loop_invariant_collectives(closed)
    assert len(found) == 1 and found[0][0] == "psum"


def test_loop_variant_collective_not_flagged(g22):
    def body(x):
        def step(c, _):
            # psum of the CARRY: genuinely per-iteration
            return c + lax.psum(c, "mc"), None
        out, _ = lax.scan(step, x, None, length=3)
        return out

    closed = jax.make_jaxpr(_smap(g22, body))(
        jax.ShapeDtypeStruct((4,), jnp.float32))
    assert find_loop_invariant_collectives(closed) == []


# ---------------------------------------------------------------------
# payload-dtype-aware byte estimates (ISSUE 8 satellite): the estimator
# reads the ACTUAL collective operand dtype(s), so convert-before-
# collective patterns (the comm_precision encode path, PR 1's bf16
# updates) are priced at their true wire bytes
# ---------------------------------------------------------------------

def test_convert_before_collective_prices_wire_dtype(g22):
    """Casting to bf16 right before the all_gather halves the estimated
    bytes: the walker must read the collective operand's aval, never
    assume the traced program's input dtype."""
    def body(x):
        return lax.all_gather(x.astype(jnp.bfloat16), ("mc", "mr"),
                              axis=0).astype(jnp.float32).sum(0)

    fn = _smap(g22, body)
    closed = jax.make_jaxpr(fn)(jax.ShapeDtypeStruct((8, 8), jnp.float32))
    evs = collect_events(closed)
    assert len(evs) == 1
    ev = evs[0]
    assert ev.dtype == "bfloat16"
    assert ev.bytes_per_call == estimate_bytes("all_gather", 8 * 8 * 2, 4)


def test_multi_operand_psum_sums_all_payloads(g22):
    """A tuple psum carries several array operands: the byte estimate
    counts every payload at its own dtype (the old first-operand shortcut
    under-reported mixed-dtype reductions).  The installed JAX traces the
    tuple as one psum equation per operand, so the claim is on the total:
    one event per equation, each at its own dtype, and nothing lost."""
    def body(x):
        a, b, c = lax.psum((x, (2 * x).astype(jnp.bfloat16), 3 * x),
                           ("mc", "mr"))
        return a + b.astype(jnp.float32) + c

    fn = _smap(g22, body)
    closed = jax.make_jaxpr(fn)(jax.ShapeDtypeStruct((8, 8), jnp.float32))
    evs = [e for e in collect_events(closed) if e.prim == "psum"]
    assert sorted(e.dtype for e in evs) == ["bfloat16", "float32", "float32"]
    nbytes = 2 * 8 * 8 * 4 + 8 * 8 * 2      # two f32 operands + one bf16
    assert sum(e.bytes_per_call for e in evs) \
        == estimate_bytes("psum", nbytes, 4)
    bf16 = next(e for e in evs if e.dtype == "bfloat16")
    assert bf16.bytes_per_call == estimate_bytes("psum", 8 * 8 * 2, 4)
