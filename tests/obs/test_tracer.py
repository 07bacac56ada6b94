"""Span tracer unit behavior: nesting/ordering, tick channels, hook
dispatch, the PhaseTimer shim's byte-compatible output (ISSUE 5)."""
import json

import pytest

from elemental_tpu import obs
from elemental_tpu.obs.tracer import NULL_HOOK, _Fanout, phase_hook


class FakeClock:
    """Deterministic monotone clock: each read advances by ``step``."""

    def __init__(self, step=1.0):
        self.t = 0.0
        self.step = step

    def __call__(self):
        self.t += self.step
        return self.t


# ---------------------------------------------------------------------
# explicit spans
# ---------------------------------------------------------------------

def test_span_nesting_and_ordering():
    tr = obs.Tracer(metrics=False, clock=FakeClock())
    with tr.span("outer", kind="run"):
        with tr.span("inner", k=1):
            pass
        with tr.span("inner2"):
            pass
    assert [s.name for s in tr.spans] == ["outer", "inner", "inner2"]
    assert [s.depth for s in tr.spans] == [0, 1, 1]
    o, i1, i2 = tr.spans
    # children are strictly contained in the parent interval and ordered
    assert o.t0 < i1.t0 < i1.t1 < i2.t0 < i2.t1 < o.t1
    assert o.attrs == {"kind": "run"} and i1.attrs == {"k": 1}


def test_span_sync_blocks_on_outputs():
    import jax.numpy as jnp
    tr = obs.Tracer(metrics=False)
    with tr.span("phase", sync=(jnp.zeros(4),)) as s:
        pass
    assert s.t1 is not None and s.t1 >= s.t0


# ---------------------------------------------------------------------
# tick channels (the driver hook protocol)
# ---------------------------------------------------------------------

def test_tick_channel_intervals():
    clock = FakeClock()
    tr = obs.Tracer(metrics=False, clock=clock)
    ch = tr.channel("lu")
    ch.start()                      # t=1
    ch.tick("panel", 0)             # t=2: [1, 2]
    ch.tick("update", 0)            # t=3: [2, 3]
    ch.tick("panel", 1)             # t=4: [3, 4]
    recs = tr.phases
    assert [(r.driver, r.phase, r.step) for r in recs] == \
        [("lu", "panel", 0), ("lu", "update", 0), ("lu", "panel", 1)]
    assert [(r.t0, r.t1) for r in recs] == [(1.0, 2.0), (2.0, 3.0), (3.0, 4.0)]
    # driver span synthesis: one call spanning first t0 .. last t1
    calls = tr.driver_calls()
    assert calls == [(1, "lu", 1.0, 4.0, [0, 1])]
    assert tr.phase_totals() == {"lu": {"panel": 2.0, "update": 1.0}}


def test_tick_without_start_charges_zero():
    tr = obs.Tracer(metrics=False, clock=FakeClock())
    ch = tr.channel("qr")
    ch.tick("panel", 0)             # unarmed: zero-length interval
    assert tr.phases[0].seconds == 0.0


def test_two_channels_are_separate_driver_calls():
    tr = obs.Tracer(metrics=False, clock=FakeClock())
    a, b = tr.channel("gemm"), tr.channel("trsm")
    a.start()
    b.start()
    a.tick("panel", 0)
    b.tick("solve", 0)
    calls = tr.driver_calls()
    assert [c[1] for c in calls] == ["gemm", "trsm"]
    assert calls[0][0] != calls[1][0]


# ---------------------------------------------------------------------
# phase_hook dispatch
# ---------------------------------------------------------------------

def test_phase_hook_null_when_inactive():
    with obs.metrics_scope() as reg:
        assert phase_hook("lu") is NULL_HOOK
        assert reg.counter_value("op_calls", op="lu") == 1


def test_phase_hook_returns_timer_when_inactive():
    t = obs.PhaseTimer()
    with obs.metrics_scope():
        assert phase_hook("cholesky", t) is t


def test_phase_hook_routes_to_active_tracer():
    tr = obs.Tracer(metrics=False)
    with obs.metrics_scope():
        with tr:
            hk = phase_hook("herk")
            hk.tick("spread", 0)
    assert [(r.driver, r.phase) for r in tr.phases] == [("herk", "spread")]
    assert obs.active_tracer() is None      # deactivated on exit


def test_phase_hook_fans_out_to_both():
    tr = obs.Tracer(metrics=False)
    t = obs.PhaseTimer()
    with obs.metrics_scope():
        with tr:
            hk = phase_hook("lu", t)
            assert isinstance(hk, _Fanout)
            hk.start()
            hk.tick("panel", 0)
    assert [r.phase for r in tr.phases] == ["panel"]
    assert [r["phase"] for r in t.records] == ["panel"]


def test_nested_activation_restores_previous():
    t1, t2 = obs.Tracer(metrics=False), obs.Tracer(metrics=False)
    with obs.metrics_scope():
        with t1:
            with t2:
                assert obs.active_tracer() is t2
            assert obs.active_tracer() is t1
    assert obs.active_tracer() is None


# ---------------------------------------------------------------------
# collective events
# ---------------------------------------------------------------------

def _fake_record(grid_shape=(2, 2)):
    from elemental_tpu.core.dist import MC, MR, STAR
    from elemental_tpu.redist.engine import RedistRecord
    return RedistRecord(kind="redistribute", src=(MC, MR), dst=(STAR, STAR),
                        gshape=(64, 64), dtype="float32", in_id=1,
                        out_ids=(2,), grid_shape=grid_shape)


def test_ring_bytes():
    assert obs.ring_bytes((64, 64), "float32", (1, 1)) == 0
    assert obs.ring_bytes((64, 64), "float32", (2, 2)) == 64 * 64 * 4 * 3 // 4
    assert obs.ring_bytes((8, 8), "float64", (2, 1)) == 8 * 8 * 8 // 2
    assert obs.ring_bytes((8, 8), "not-a-dtype", (2, 2)) == 8 * 8 * 4 * 3 // 4


def test_comm_event_attribution_and_metrics():
    tr = obs.Tracer()
    with obs.metrics_scope() as reg:
        with tr:
            ch = tr.channel("cholesky")
            ch.start()
            with tr.span("step0"):
                tr._on_redist(_fake_record())
    ev = tr.comms[0]
    assert ev.label == "[MC,MR]->[STAR,STAR]"
    assert ev.span == "step0" and ev.driver == "cholesky"
    assert ev.bytes == 64 * 64 * 4 * 3 // 4
    assert tr.redist_counts() == {"[MC,MR]->[STAR,STAR]": 1}
    assert reg.counter_value("redist_calls",
                             label="[MC,MR]->[STAR,STAR]") == 1
    assert reg.counter_value("redist_bytes",
                             label="[MC,MR]->[STAR,STAR]") == ev.bytes


def test_engine_observer_fires_on_real_redistribute(grid24):
    import numpy as np
    import elemental_tpu as el
    A = el.from_global(np.arange(64.0).reshape(8, 8), el.MC, el.MR,
                       grid=grid24)
    tr = obs.Tracer(metrics=False)
    with obs.metrics_scope():
        with tr:
            el.redistribute(A, el.STAR, el.STAR)
    assert tr.redist_counts() == {"[MC,MR]->[STAR,STAR]": 1}
    # observer removed on exit: further redistributes are not recorded
    with obs.metrics_scope():
        el.redistribute(A, el.VC, el.STAR)
    assert sum(tr.redist_counts().values()) == 1


# ---------------------------------------------------------------------
# PhaseTimer shim (byte-compatible phase_timings/v1)
# ---------------------------------------------------------------------

def test_phase_timer_shim_reexport_identity():
    from elemental_tpu.obs import PHASES, PhaseTimer
    from elemental_tpu.obs import PHASE_TIMINGS_SCHEMA as SCHEMA
    from elemental_tpu.obs import phase_timer as obs_pt
    assert PhaseTimer is obs_pt.PhaseTimer
    assert SCHEMA == obs_pt.SCHEMA == "phase_timings/v1"
    assert PHASES == obs_pt.PHASES


def test_phase_timer_report_structure():
    t = obs.PhaseTimer(tracer=obs.Tracer(metrics=False, clock=FakeClock()))
    t.start()                       # t=1
    t.tick("panel", 0)              # [1,2] -> 1.0
    t.tick("swap", 0)               # [2,3] -> 1.0
    t.tick("panel", 1)              # [3,4] -> 1.0
    t.tick("update", 0)             # [4,5] -> 1.0
    doc = json.loads(t.json(driver="lu", n=64, nb=16))
    assert doc == {
        "schema": "phase_timings/v1",
        "steps": [{"step": 0, "panel": 1.0, "swap": 1.0, "update": 1.0},
                  {"step": 1, "panel": 1.0}],
        "totals": {"panel": 2.0, "swap": 1.0, "update": 1.0},
        "total_seconds": 4.0,
        "driver": "lu", "n": 64, "nb": 16,
    }
    # canonical phase ordering in totals (diag..tail first, extras after)
    assert list(doc["totals"]) == ["panel", "swap", "update"]
    assert t.records == [
        {"phase": "panel", "step": 0, "seconds": 1.0},
        {"phase": "swap", "step": 0, "seconds": 1.0},
        {"phase": "panel", "step": 1, "seconds": 1.0},
        {"phase": "update", "step": 0, "seconds": 1.0},
    ]


def test_phase_timer_tick_before_start_is_zero():
    t = obs.PhaseTimer(tracer=obs.Tracer(metrics=False, clock=FakeClock()))
    t.tick("panel", 0)
    assert t.records == [{"phase": "panel", "step": 0, "seconds": 0.0}]


@pytest.mark.parametrize("driver", ["qr", "gemm", "trsm", "herk"])
def test_new_driver_hooks_emit_phases(driver, grid24):
    """The four newly instrumented drivers emit spans under an active
    tracer (cholesky/lu are covered by tests/perf and the cross-check)."""
    import numpy as np
    import elemental_tpu as el
    n, nb = 16, 8
    rng = np.random.default_rng(3)
    F = rng.normal(size=(n, n))
    S = F @ F.T / n + n * np.eye(n)
    A = el.from_global(S, el.MC, el.MR, grid=grid24)
    B = el.from_global(F, el.MC, el.MR, grid=grid24)
    tr = obs.Tracer(metrics=False)
    with obs.metrics_scope():
        with tr:
            if driver == "qr":
                el.qr(B, nb=nb)
            elif driver == "gemm":
                el.gemm(B, B, alg="C", nb=nb)
            elif driver == "trsm":
                el.trsm("L", "L", "N", A, B, nb=nb)
            else:
                el.herk("L", B, nb=nb)
    drivers = {r.driver for r in tr.phases}
    assert drivers == {driver}
    assert len(tr.phases) >= 1
    # phases nest under synthesized per-step spans with monotone intervals
    for r in tr.phases:
        assert r.t1 >= r.t0
