"""``PhaseTimer``: per-phase wall-clock attribution (``phase_timings/v1``).

A THIN SHIM over the span tracer (:mod:`.tracer`): ticks land as the
tracer's :class:`~elemental_tpu.obs.tracer.PhaseRecord` intervals and the
report aggregates them into the ``phase_timings/v1`` document
(``tests/perf/test_phase_smoke.py`` pins the schema).

Any driver that accepts a ``timer`` argument closes its phases through
the hook's scoped form (``with timer.phase(phase, step) as ph: ...;
ph.done(*arrays)``), whose exit calls ``timer.tick(phase, step,
*arrays)``.  The timer synchronizes on the phase's outputs
(``jax.block_until_ready``) and charges the elapsed wall-clock since the
previous tick to ``(phase, step)``, so a run yields a machine-readable
breakdown per blocked step.

Usage (EAGER -- under jit the driver fuses into one program; its phases
then show in a device trace by their scope names, not here)::

    from elemental_tpu.obs import PhaseTimer
    t = PhaseTimer()
    LU, perm = el.lu(A, nb=2048, timer=t)
    print(t.json(driver="lu", n=n, nb=2048))

``python -m perf.trace`` is the CLI (nested spans + collective events +
Perfetto export).  Schema (``phase_timings/v1``; LU
emits panel/swap/solve/update, Cholesky diag/panel/spread/update and
``tail`` on the crossover step)::

    {"schema": "phase_timings/v1",
     "steps":  [{"step": 0, "panel": s, "swap": s, "solve": s, "update": s},
                ...],                      # seconds; phases may be absent
     "totals": {"panel": s, "swap": s, "solve": s, "update": s},
     "total_seconds": s,
     ...caller metadata (driver, n, nb, device, ...)}

Timing note: eager dispatch is asynchronous, so the sync INSIDE tick is
what makes the attribution honest; each phase's time includes its share of
dispatch overhead (the same caveat as any op-by-op profile).  The fused
program is timed by ``benchmark/run.py``.
"""
from __future__ import annotations

import json

from .tracer import PhaseHook, Tracer

SCHEMA = "phase_timings/v1"

#: canonical phase order for reports (drivers emit a subset: LU ticks
#: panel/swap/solve/update, Cholesky diag/panel/spread/update + tail,
#: QR panel/update, gemm panel, trsm solve/update, herk spread/update;
#: the Hermitian eigensolve names hemv/panel/update in the reduction,
#: leaf/secular/fill/merge in the divide and conquer, apply in the
#: back-transform; the mixed-precision solve's refinement names
#: residual/correct)
PHASES = ("diag", "panel", "swap", "solve", "spread", "update", "tail",
          "hemv", "leaf", "secular", "fill", "merge", "apply",
          "local", "tree", "applyq", "residual", "correct")


class PhaseTimer(PhaseHook):
    """Accumulates (phase, step, seconds) records from a driver's ticks.

    Backed by a private (metrics-silent) :class:`Tracer` whose tick
    channel does the sync + interval bookkeeping; an externally supplied
    ``tracer`` lets callers merge PhaseTimer ticks into a larger trace.
    """

    def __init__(self, tracer: Tracer | None = None):
        self.tracer = tracer if tracer is not None else Tracer(metrics=False)
        self._chan = self.tracer.channel("phase_timer")
        self._chan._t = None            # unarmed until start()/first tick

    def start(self):
        """(Re)arm the clock at a driver's entry."""
        self._chan.start()

    def tick(self, phase, step, *arrays):
        """Block on ``arrays`` and charge the elapsed time to (phase, step)."""
        self._chan.tick(phase, step, *arrays)

    @property
    def records(self) -> list[dict]:
        """The historical record shape: [{"phase", "step", "seconds"}]."""
        return [{"phase": r.phase, "step": r.step, "seconds": r.seconds}
                for r in self.tracer.phases if r.call == self._chan.call]

    def report(self, **meta) -> dict:
        """The schema dict above; ``meta`` keys merge at top level."""
        steps: dict[int, dict] = {}
        totals: dict[str, float] = {}
        for r in self.records:
            d = steps.setdefault(r["step"], {})
            d[r["phase"]] = d.get(r["phase"], 0.0) + r["seconds"]
            totals[r["phase"]] = totals.get(r["phase"], 0.0) + r["seconds"]
        out = {
            "schema": SCHEMA,
            "steps": [{"step": k, **v} for k, v in sorted(steps.items())],
            "totals": {p: totals[p] for p in PHASES if p in totals}
            | {p: t for p, t in totals.items() if p not in PHASES},
            "total_seconds": sum(totals.values()),
        }
        out.update(meta)
        return out

    def json(self, **meta) -> str:
        return json.dumps(self.report(**meta))
