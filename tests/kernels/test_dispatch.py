"""The ``panel_impl`` knob (ISSUE 17): plan resolution, the static
VMEM/dtype dispatch gate, complex fallback, and driver integration.
"""
import numpy as np
import pytest

import jax.numpy as jnp

import elemental_tpu as el
from elemental_tpu import MC, MR, from_global, to_global
from elemental_tpu.kernels import (DEFAULT_INNERS, PANEL_IMPLS, PanelPlan,
                                   default_inners, panel_fits, resolve_panel)

from ..conftest import compiled


def _dist(g, arr):
    return from_global(arr, MC, MR, grid=g)


# ---------------------------------------------------------------- plan

def test_resolve_defaults():
    plan = resolve_panel(None)
    assert plan.impl == "xla" and plan.source == "default"
    assert plan.inners == DEFAULT_INNERS == default_inners()
    assert resolve_panel("pallas").source == "explicit"
    with pytest.raises(ValueError, match="panel_impl"):
        resolve_panel("mosaic")


def test_unresolved_auto_is_refused_not_sent_to_xla():
    # 'auto' is the tuner's to resolve; reaching the dispatch with it is a
    # driver bug, and quietly running the XLA ladder would hide it
    with pytest.raises(ValueError, match="auto"):
        resolve_panel("auto")


@pytest.mark.parametrize("backend,interpret,expect", [
    ("cpu", None, True), ("cpu", False, False), ("cpu", True, True),
    ("tpu", None, False), ("tpu", False, False),
    ("tpu", True, ValueError), ("gpu", None, NotImplementedError)])
def test_interpret_policy_per_backend(monkeypatch, backend, interpret,
                                      expect):
    # CPU interprets (the tests) unless told to lower; a TPU compiles or
    # fails; any other backend has no lowering and is refused
    import jax
    from elemental_tpu.kernels import interpret_default
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if isinstance(expect, bool):
        assert interpret_default(interpret) is expect
    else:
        with pytest.raises(expect):
            interpret_default(interpret)


def test_complex_resolves_to_xla_silently():
    plan = resolve_panel("pallas", dtype=jnp.complex64)
    assert plan.impl == "xla" and plan.source == "complex-xla"


def test_vmem_gate():
    plan = PanelPlan(impl="pallas")
    assert plan.use_pallas((512, 64), jnp.float32)
    # a panel whose padded working set exceeds the 16 MiB budget must
    # route back to xla -- the fused kernel never silently spills
    assert not plan.use_pallas((1 << 20, 2048), jnp.float32)
    assert not panel_fits((1 << 20, 2048), jnp.float32)
    assert not plan.use_pallas((64, 16), jnp.complex64)
    assert not PanelPlan(impl="xla").use_pallas((64, 16), jnp.float32)


def test_inners_flow_through_plan():
    plan = resolve_panel(None, inners=(768, 96))
    assert plan.inners == (768, 96)
    assert plan.pallas_inner == 96


# ------------------------------------------------------------- tuning

def test_registry_has_panel_impl():
    from elemental_tpu.tune.knobs import OPS
    from elemental_tpu.tune.knobs import PANEL_IMPLS as KNOB_IMPLS
    assert KNOB_IMPLS == PANEL_IMPLS          # mirrored literal stays pinned
    for op in ("lu", "cholesky", "qr"):
        assert "panel_impl" in OPS[op].knobs


def test_auto_resolves_xla_on_cpu_pallas_on_tpu(grid24):
    from elemental_tpu.tune import cost_model as cm
    from elemental_tpu.tune.knobs import TuneContext, candidate_configs

    def best(op, backend, machine):
        ctx = TuneContext(op=op, dims=(64, 64), dtype="float32",
                          grid_shape=(2, 2), backend=backend)
        scored = [cm.score_config(op, cfg, ctx=ctx, grid=grid24,
                                  dtype=jnp.float32, machine=machine)
                  for cfg in candidate_configs(ctx)]
        order = sorted(range(len(scored)),
                       key=lambda i: (scored[i].total_s, i))
        return scored[order[0]].config["panel_impl"]

    for op in ("lu", "cholesky", "qr"):
        assert best(op, "cpu", cm.MACHINES["cpu"]) == "xla", op
        assert best(op, "tpu", cm.MACHINES["tpu"]) == "pallas", op


def test_complex_space_is_xla_only():
    from elemental_tpu.tune.knobs import TuneContext, candidate_configs
    ctx = TuneContext(op="cholesky", dims=(64, 64), dtype="complex128",
                      grid_shape=(2, 2), backend="cpu")
    assert {c["panel_impl"] for c in candidate_configs(ctx)} == {"xla"}


# ------------------------------------------------------------- drivers

def test_lu_pallas_matches_xla_pivots(two_grids):
    rng = np.random.default_rng(17)
    F = rng.normal(size=(32, 32))
    A = _dist(two_grids, F)
    LUp, permp = compiled(el.lu, nb=8, panel_impl="pallas")(A)
    LUx, permx = compiled(el.lu, nb=8, panel_impl="xla")(A)
    np.testing.assert_array_equal(np.asarray(permp), np.asarray(permx))
    lu_ = np.asarray(to_global(LUp))
    L = np.tril(lu_, -1) + np.eye(32)
    U = np.triu(lu_)
    assert np.linalg.norm(L @ U - F[np.asarray(permp)]) \
        / np.linalg.norm(F) < 1e-12


def test_cholesky_pallas_residual(two_grids):
    rng = np.random.default_rng(18)
    G = rng.normal(size=(32, 32))
    S = G @ G.T / 32 + 32 * np.eye(32)
    L = compiled(el.cholesky, nb=8, panel_impl="pallas")(_dist(two_grids, S))
    lg = np.asarray(to_global(L))
    assert np.linalg.norm(lg @ lg.T - S) / np.linalg.norm(S) < 1e-12


def test_qr_pallas_matches_xla(two_grids):
    rng = np.random.default_rng(19)
    F = rng.normal(size=(32, 32))
    A = _dist(two_grids, F)
    pp, taup = compiled(el.qr, nb=8, panel_impl="pallas")(A)
    px, taux = compiled(el.qr, nb=8, panel_impl="xla")(A)
    np.testing.assert_allclose(np.asarray(to_global(pp)),
                               np.asarray(to_global(px)),
                               rtol=0, atol=1e-11)
    np.testing.assert_allclose(np.asarray(taup), np.asarray(taux),
                               rtol=0, atol=1e-13)


def test_complex_driver_falls_back_bitwise(grid24):
    # panel_impl='pallas' on a complex matrix must factor (never raise)
    # and produce EXACTLY the xla path's bits -- the knob is a
    # performance hint, not a semantics switch
    rng = np.random.default_rng(20)
    F = (rng.normal(size=(24, 24)) + 1j * rng.normal(size=(24, 24)))
    A = _dist(grid24, F)
    LUp, permp = compiled(el.lu, nb=8, panel_impl="pallas")(A)
    LUx, permx = compiled(el.lu, nb=8, panel_impl="xla")(A)
    np.testing.assert_array_equal(np.asarray(permp), np.asarray(permx))
    assert np.array_equal(np.asarray(to_global(LUp)),
                          np.asarray(to_global(LUx)))


def test_driver_accepts_panel_impl_auto(grid24):
    rng = np.random.default_rng(21)
    F = rng.normal(size=(24, 24))
    LU, perm = compiled(el.lu, nb=8, panel_impl="auto")(_dist(grid24, F))
    lu_ = np.asarray(to_global(LU))
    L = np.tril(lu_, -1) + np.eye(24)
    U = np.triu(lu_)
    assert np.linalg.norm(L @ U - F[np.asarray(perm)]) \
        / np.linalg.norm(F) < 1e-12


def test_abft_composes_with_pallas(grid24):
    rng = np.random.default_rng(22)
    F = rng.normal(size=(24, 24))
    LU, perm = el.lu(_dist(grid24, F), nb=8, panel_impl="pallas",
                     abft=True)
    lu_ = np.asarray(to_global(LU))
    L = np.tril(lu_, -1) + np.eye(24)
    U = np.triu(lu_)
    assert np.linalg.norm(L @ U - F[np.asarray(perm)]) \
        / np.linalg.norm(F) < 1e-12
