"""AsyncSolverService (ISSUE 14 tentpole): the double-buffered pipeline
must be semantically invisible -- bit-identical solutions and unchanged
``serve_result/v1`` docs vs the sync core -- while completions stream,
deadlines keep their semantics under concurrency, the breaker stays
deterministic under an injected clock, and shutdown never leaks the
worker thread or silently drops a future."""
import threading

import numpy as np

from elemental_tpu.obs import metrics as _metrics
from elemental_tpu.serve import (AsyncSolverService, SolverService,
                                 donation_safe, serve_async)

from .conftest import FakeClock, diag_dom, spd

#: serve_result/v1 keys that must be identical sync vs async (timing
#: keys excluded -- wall clock legitimately differs)
SEM_KEYS = ("op", "n", "nrhs", "bucket", "status", "path", "rung",
            "residual", "tol", "retries", "bisected", "timed_out")


def _workload(rng, count=10):
    out = []
    for i in range(count):
        n = (12, 16, 9)[i % 3]
        if i % 2:
            out.append(("lu", diag_dom(rng, n), rng.normal(size=(n, 2))))
        else:
            out.append(("hpd", spd(rng, n), rng.normal(size=(n, 2))))
    return out


def _no_leak():
    return not any(t.name.startswith("elemental-serve-worker") and t.is_alive()
                   for t in threading.enumerate())


def test_async_bit_identical_to_sync(grid24):
    """The pipelined front (donated buffers, overlapped staging) returns
    bit-identical solutions and semantically identical docs for the same
    workload as the synchronous core."""
    rng = np.random.default_rng(40)
    work = _workload(rng, count=10)
    sync = SolverService(grid24, max_batch=4)
    rids = [sync.submit(op, A, B) for op, A, B in work]
    sdocs = sync.drain()

    front = AsyncSolverService(grid=grid24, max_batch=4)
    futs = [front.submit(op, A, B) for op, A, B in work]
    outs = [f.result(timeout=300.0) for f in futs]
    front.shutdown()
    for rid, (x2, d2) in zip(rids, outs):
        d1 = sdocs[rid]
        for k in SEM_KEYS:
            assert d1[k] == d2[k], k
        assert d1["dispatch"]["route"] == d2["dispatch"]["route"]
        x1 = sync.solutions[rid]
        assert x1.dtype == x2.dtype
        np.testing.assert_array_equal(x1, x2)
    assert _no_leak()


def test_completions_stream_before_shutdown(grid24):
    """Futures resolve as their batch certifies -- not at shutdown --
    and pre-registered callbacks fire on the worker thread; callbacks
    added AFTER resolution fire immediately on the caller's thread."""
    rng = np.random.default_rng(41)
    front = AsyncSolverService(grid=grid24, max_batch=2)
    seen: list = []
    futs = [front.submit(op, A, B, callback=lambda f: seen.append(
                (f.id, threading.current_thread().name)))
            for op, A, B in _workload(rng, count=6)]
    outs = [f.result(timeout=300.0) for f in futs]
    # every future resolved while the service is still accepting
    assert all(f.done() for f in futs) and not front._stop
    assert all(d["status"] == "ok" for _, d in outs)
    late: list = []
    futs[0].add_done_callback(lambda f: late.append(
        threading.current_thread().name))
    assert late == [threading.current_thread().name]   # immediate, caller
    front.shutdown()
    assert sorted(i for i, _ in seen) == sorted(f.id for f in futs)
    assert {name for _, name in seen} == {"elemental-serve-worker"}
    assert _no_leak()


def test_expired_at_ingest_rejects_while_mates_complete(grid24):
    """A deadline that lapses in the SUBMISSION queue (before admission)
    resolves with the structured serve_reject/v1 while its batch-mates
    complete ok -- deterministic via the injected clock."""
    clk = FakeClock()
    rng = np.random.default_rng(42)
    svc = SolverService(grid24, clock=clk, sleep=clk.sleep, max_batch=4)
    front = AsyncSolverService(svc, autostart=False)
    A, B = diag_dom(rng, 12), rng.normal(size=(12, 2))
    f_ok = front.submit("lu", A, B)                    # no budget
    f_dead = front.submit("lu", A, B, budget_s=1.0)
    clk.advance(2.0)                                   # lapses queued
    front.start()
    x1, d1 = f_ok.result(timeout=300.0)
    x2, d2 = f_dead.result(timeout=300.0)
    front.shutdown()
    assert d1["status"] == "ok" and x1 is not None
    assert d2["schema"] == "serve_reject/v1"
    assert d2["reason"] == "deadline_expired" and x2 is None
    assert d2["deadline"]["remaining_s"] < 0
    assert _no_leak()


def test_deadline_lapse_mid_pipeline_drops_structured(grid24):
    """A deadline that lapses AFTER admission, while earlier batches are
    in flight, is finalized as a structured timed_out serve_result (path
    'dropped') without paying a dispatch -- batch-mates unaffected.  The
    clock advances inside batch 0's completion callback (worker thread),
    which double buffering orders after batch 1's dispatch and before
    batch 2's staging: fully deterministic."""
    clk = FakeClock()
    rng = np.random.default_rng(43)
    svc = SolverService(grid24, clock=clk, sleep=clk.sleep, max_batch=1)
    front = AsyncSolverService(svc, autostart=False)
    A, B = diag_dom(rng, 12), rng.normal(size=(12, 2))
    f0 = front.submit("lu", A, B, callback=lambda f: clk.advance(2.0))
    f1 = front.submit("lu", A, B)
    f2 = front.submit("lu", A, B, budget_s=1.0)        # dies in queue
    front.start()
    front.shutdown(drain=True)
    assert f0.result(timeout=0)[1]["status"] == "ok"
    assert f1.result(timeout=0)[1]["status"] == "ok"
    x2, d2 = f2.result(timeout=0)
    assert d2["status"] == "timed_out" and d2["path"] == "dropped"
    assert d2["timed_out"] is True and x2 is None
    assert d2["deadline"]["remaining_s"] < 0
    assert f2.id not in svc.solutions                  # never dispatched
    assert _no_leak()


def test_breaker_deterministic_under_pipelining(grid24):
    """The pipelining price, pinned: batch k+1's fastpath decision is
    made BEFORE batch k's outcome lands, so the request staged while the
    trip was in flight still certifies on the fastpath; the next batch
    sees the open breaker and bypasses to escalation; the racing
    request's success then closes the breaker again (collected after the
    trip).  Bit-deterministic across runs under the injected clock."""
    rng = np.random.default_rng(44)
    n = 8
    Asing = np.ones((n, n))
    Agood = diag_dom(rng, n)
    B = rng.normal(size=(n, 1))

    def run_once():
        clk = FakeClock()
        svc = SolverService(grid24, clock=clk, sleep=clk.sleep,
                            breaker_threshold=1, breaker_cooldown_s=1e9,
                            retries=0, max_batch=1)
        front = AsyncSolverService(svc, autostart=False)
        f_bad = front.submit("lu", Asing, B)
        f_racing = front.submit("lu", Agood, B)   # staged during the trip
        f_after = front.submit("lu", Agood, B)    # staged after the trip
        front.start()
        front.shutdown(drain=True)
        db = f_bad.result(timeout=0)[1]
        dr = f_racing.result(timeout=0)[1]
        da = f_after.result(timeout=0)[1]
        key = "lu__b8x1__float64"
        return (db["status"], dr["status"], dr["path"], da["status"],
                da["path"], svc.breakers[key].state,
                f_racing.result(timeout=0)[0].tobytes(),
                f_after.result(timeout=0)[0].tobytes())

    r1 = run_once()
    r2 = run_once()
    assert r1 == r2                                # deterministic replay
    # batch 1 rode the fastpath (staged pre-trip), batch 2 saw the open
    # breaker and escalated, and batch 1's collected success closed it
    assert r1[:6] == ("failed", "ok", "fastpath", "ok", "escalated",
                      "closed")
    assert _no_leak()


def test_donation_gated_to_accelerator_backends(grid24, monkeypatch):
    """``donate=True`` is honored only where :func:`donation_safe` says
    the backend donates correctly under overlapped dispatch: never on
    the CPU client (whose donated buffers can be recycled while batch k
    is still in flight), always on accelerators."""
    import jax
    assert donation_safe() is (jax.default_backend() != "cpu")
    front = AsyncSolverService(grid=grid24, autostart=False, donate=True)
    assert front.donate is donation_safe()
    front.shutdown()
    from elemental_tpu.serve import async_front
    monkeypatch.setattr(async_front, "donation_safe", lambda: True)
    front = AsyncSolverService(grid=grid24, autostart=False, donate=True)
    assert front.donate is True
    front.shutdown()
    front = AsyncSolverService(grid=grid24, autostart=False)
    assert front.donate is True                    # donation is the default
    front.shutdown()
    front = AsyncSolverService(grid=grid24, autostart=False, donate=False)
    assert front.donate is False                   # explicit opt-out wins
    front.shutdown()
    assert _no_leak()


def test_shutdown_drain_false_flushes_structured(grid24):
    """Emergency stop: everything still queued resolves with a
    structured shutdown reject -- zero silent drops -- and post-shutdown
    submissions resolve immediately with the same."""
    rng = np.random.default_rng(45)
    front = AsyncSolverService(grid=grid24, autostart=False, max_batch=2)
    futs = [front.submit(op, A, B) for op, A, B in _workload(rng, 6)]
    with _metrics.scoped() as reg:
        done = front.shutdown(drain=False)
        assert reg.counter_value("serve_rejects", reason="shutdown") == 6
    assert done == {}                              # nothing was admitted
    for f in futs:
        x, doc = f.result(timeout=0)
        assert x is None
        assert doc["schema"] == "serve_reject/v1"
        assert doc["reason"] == "shutdown"
    assert front.service.solutions == {}           # nothing executed
    assert _no_leak()
    assert front.shutdown() == {}                  # idempotent
    f = front.submit("lu", diag_dom(rng, 8), rng.normal(size=(8, 1)))
    assert f.done()
    assert f.result(timeout=0)[1]["reason"] == "shutdown"


def test_shutdown_drain_true_completes_everything(grid24):
    """Graceful stop: queued work COMPLETES through the pipeline; the
    returned ledger covers every admitted id."""
    rng = np.random.default_rng(46)
    front = AsyncSolverService(grid=grid24, autostart=False, max_batch=2)
    futs = [front.submit(op, A, B) for op, A, B in _workload(rng, 6)]
    done = front.shutdown(drain=True)
    assert all(f.done() for f in futs)
    assert set(done) == {f.id for f in futs}
    assert all(d["status"] == "ok" for _, d in
               (f.result(timeout=0) for f in futs))
    assert _no_leak()


def test_serve_async_convenience(grid24):
    rng = np.random.default_rng(47)
    work = _workload(rng, 5)
    docs, xs = serve_async(work, grid=grid24)
    assert len(docs) == len(xs) == 5
    for (op, A, B), doc, x in zip(work, docs, xs):
        assert doc["status"] == "ok" and doc["op"] == op
        np.testing.assert_allclose(x, np.linalg.solve(A, B),
                                   rtol=1e-8, atol=1e-10)
    assert _no_leak()


def test_pipeline_stats_and_gauges(grid24):
    rng = np.random.default_rng(48)
    with _metrics.scoped() as reg:
        front = AsyncSolverService(grid=grid24, max_batch=2)
        futs = [front.submit(op, A, B) for op, A, B in _workload(rng, 8)]
        for f in futs:
            f.result(timeout=300.0)
        front.shutdown()
        stats = front.pipeline_stats()
        assert stats["wall_s"] >= 0.0 and stats["device_busy_s"] >= 0.0
        # busy windows open at dispatch-call time, the wall clock starts
        # once the first dispatch returns -- occupancy may nose slightly
        # above 1.0, never wildly
        assert 0.0 <= stats["occupancy"] <= 1.2
        gauges = {r["name"]: r["value"] for r in reg.to_doc()["gauges"]}
        assert "serve_pipeline_occupancy" in gauges
        assert gauges["serve_async_inflight"] == 0
        assert gauges["serve_async_submit_queue"] == 0
    assert _no_leak()
