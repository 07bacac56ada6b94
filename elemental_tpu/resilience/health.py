"""Numerical-health guards: cheap per-phase checks -> ``health_report/v1``.

The detection half of the resilience subsystem (ISSUE 7).  A
:class:`HealthMonitor` speaks the PhaseTimer tick protocol (``start()`` +
``tick(phase, step, *arrays)``), so it rides the SAME driver hook seam the
observability subsystem built (ISSUE 5): ``lu(..., health=...)`` /
``cholesky(..., health=...)`` fan the monitor into the phase hook next to
any explicit timer / active tracer, and every phase boundary the driver
already ticks becomes a checkpoint.  With ``health=None`` (the default)
NOTHING is attached -- the drivers keep the zero-overhead NULL_HOOK path,
pinned by the redist-count and comm-plan goldens.

Checks (all engine-free: pure reductions on the ticked arrays, no
redistribute/panel_spread entries, so the comm plan of a monitored run is
identical to an unmonitored one):

  * **NaN/Inf scan** -- every inexact-dtype leaf of every tick is
    ``isfinite``-reduced; the first non-finite phase is what a corrupted
    collective payload (see :mod:`.faults`) surfaces as.
  * **Growth estimate** -- running ``max |ticked panel/update| / max |A|``,
    the practical stand-in for the factorization growth factor.  CALU's
    tournament trades partial pivoting's ``2^k`` bound for a
    ``2^{nb log2 r}``-class one (ISSUE 6's documented caveat); this is
    the guard that notices when that trade goes wrong at runtime.
  * **Diagonal checks** -- driver-aware: LU's packed ``panel`` ticks carry
    the pivots on the diagonal (near-zero pivot == (near-)singular);
    Cholesky's ``diag`` ticks carry L11 (non-positive / near-zero
    diagonal == not positive definite; an outright non-PD block already
    NaNs out of ``jnp.linalg.cholesky`` and is caught by the scan).

Evaluation is DEFERRED: ticks record jnp scalars (one reduction per leaf,
no host sync per phase); :meth:`HealthMonitor.report` converts them once,
builds the structured ``health_report/v1`` document, bumps
``health_checks``/``health_flags`` on the current obs metrics registry,
and -- when a :class:`~elemental_tpu.obs.tracer.Tracer` is active --
attaches one ``health:<kind>`` instant event per flag to the trace.
Like the tracer, the monitor is an EAGER-mode tool: under jit the ticked
leaves are tracers and the checks degrade to no-ops.

``health_report/v1``::

    {"schema": "health_report/v1", "driver": "lu", "ok": false,
     "checks": 12,                       # ticks inspected
     "flags": [{"kind": "nonfinite", "phase": "update", "step": 3,
                "value": null}, ...],    # kinds: nonfinite | growth |
                                         #   small_pivot | nonpositive_diag
     "growth_estimate": 1.8,             # max |intermediate| / max |A|
     "scale": 3.2,                       # max |A| (the growth anchor)
     "min_diag": 0.41,                   # worst diagonal seen (driver units)
     "failing_phase": "update" | null}   # first flagged phase
"""
from __future__ import annotations

import dataclasses

import numpy as np

from ..obs.tracer import NULL_HOOK, PhaseHook, _Fanout

HEALTH_SCHEMA = "health_report/v1"

#: growth-estimate flag threshold: |intermediate| exceeding ``max|A|`` by
#: this factor marks the factorization as suspect (partial pivoting keeps
#: the ratio near O(n); a corrupted payload or a lost CALU tournament
#: lands orders of magnitude beyond it)
GROWTH_LIMIT = 1e8

#: phases whose FIRST inexact leaf carries a meaningful diagonal, per
#: driver: LU packs the pivots on the panel diagonal, Cholesky factors
#: L11 in the diag phase, and QR's packed panel carries R's diagonal
#: (the larfg betas -- near-zero == rank-deficient, the ``small_pivot``
#: flag; ISSUE 9 parity).  Other drivers get scan + growth only.
DIAG_PHASES = {"lu": ("panel",), "cholesky": ("diag",), "qr": ("panel",)}


def _is_tracer(x) -> bool:
    import jax
    return isinstance(x, jax.core.Tracer)


def _float_leaves(arrays):
    """Inexact-dtype array leaves of a tick payload (DistMatrix flattens
    to its storage array; int perm vectors are skipped)."""
    import jax
    import jax.numpy as jnp
    out = []
    for leaf in jax.tree_util.tree_leaves(arrays):
        try:
            dt = jnp.asarray(leaf).dtype if not hasattr(leaf, "dtype") \
                else leaf.dtype
        except (TypeError, ValueError):
            continue
        if jnp.issubdtype(dt, jnp.inexact):
            out.append(leaf)
    return out


@dataclasses.dataclass
class _Check:
    """One deferred per-tick observation (jnp scalars until report())."""
    phase: str
    step: int
    finite: object          # jnp bool: all leaves finite
    maxabs: object | None   # jnp scalar: max |leaf| over inexact leaves
    diag_min: object | None  # jnp scalar: min pivot/diag magnitude
    diag_signed: object | None  # jnp scalar: min REAL diag (cholesky sign)


class HealthMonitor(PhaseHook):
    """Tick-protocol numerical-health guard (see module docstring).

    Reusable as the ``health=`` argument of ``lu``/``cholesky`` (the
    driver binds the name and input scale at entry) and directly by
    :func:`~elemental_tpu.resilience.certify.certified_solve`, which
    runs one monitor per escalation-ladder attempt.
    """

    def __init__(self, growth_limit: float = GROWTH_LIMIT,
                 diag_rtol: float | None = None):
        self.growth_limit = float(growth_limit)
        self.diag_rtol = diag_rtol        # None: 8*eps(dtype) at report time
        self.driver: str | None = None
        self._scale = None                # deferred jnp max |A|
        self._eps = None
        self._checks: list[_Check] = []
        self._extra_flags: list[dict] = []
        self._emitted = False
        self._report = None

    # ---- driver binding ---------------------------------------------
    def begin(self, driver: str, scale_from=None) -> "HealthMonitor":
        """Bind the driver name and the growth anchor ``max |A|`` (one
        deferred reduction on the input storage).  Called by the driver's
        ``health=`` plumbing; rebinding RESETS the monitor -- one monitor
        covers one driver invocation (read ``report()`` between runs)."""
        import jax.numpy as jnp
        self.driver = str(driver)
        self._checks = []
        self._extra_flags = []
        self._report = None
        self._emitted = False
        if scale_from is not None and not _is_tracer(scale_from):
            arr = getattr(scale_from, "local", scale_from)
            if not _is_tracer(arr) and getattr(arr, "size", 0):
                self._scale = jnp.max(jnp.abs(arr))
                self._eps = float(jnp.finfo(arr.dtype).eps) \
                    if jnp.issubdtype(arr.dtype, jnp.inexact) else None
        return self

    # ---- PhaseTimer protocol ----------------------------------------
    def start(self):
        pass

    def tick(self, phase, step, *arrays):
        import jax.numpy as jnp
        leaves = _float_leaves(arrays)
        if not leaves or any(_is_tracer(x) for x in leaves):
            return                        # under jit / nothing to check
        fin = None
        mx = None
        for leaf in leaves:
            if leaf.size == 0:
                continue
            f = jnp.all(jnp.isfinite(leaf))
            fin = f if fin is None else jnp.logical_and(fin, f)
            a = jnp.max(jnp.abs(leaf))
            mx = a if mx is None else jnp.maximum(mx, a)
        if fin is None:
            return
        dmin = dsigned = None
        if str(phase) in DIAG_PHASES.get(self.driver or "", ()):
            d = jnp.diagonal(leaves[0])
            if d.size:
                dmin = jnp.min(jnp.abs(d))
                dsigned = jnp.min(jnp.real(d))
        self._checks.append(_Check(str(phase), int(step), fin, mx,
                                   dmin, dsigned))

    def flag(self, kind: str, phase: str, step: int, value=None) -> None:
        """Append an externally-detected flag (ISSUE 11: the ABFT guard
        pushes UNRECOVERED checksum violations here, kind ``"abft"``, so
        they surface through the same ``health_report/v1`` document and
        ``failing_phase`` plumbing as the monitor's own checks).  Must be
        called before :meth:`report` caches."""
        self._extra_flags.append({"kind": str(kind), "phase": str(phase),
                                  "step": int(step), "value": value})

    # ---- report ------------------------------------------------------
    @property
    def checks(self) -> int:
        return len(self._checks)

    def report(self, emit: bool = True) -> dict:
        """Evaluate the deferred checks into a ``health_report/v1`` doc.

        The first call (with ``emit=True``) also bumps the obs metrics
        registry and attaches ``health:<kind>`` instant events to the
        active tracer; later calls return the cached document."""
        if self._report is not None:
            return self._report
        flags = list(self._extra_flags)
        scale = float(np.asarray(self._scale)) if self._scale is not None \
            else None
        gmax = None
        min_diag = None
        for ck in self._checks:
            if not bool(np.asarray(ck.finite)):
                flags.append({"kind": "nonfinite", "phase": ck.phase,
                              "step": ck.step, "value": None})
                continue                  # maxabs of a NaN tick is noise
            if ck.maxabs is not None:
                v = float(np.asarray(ck.maxabs))
                gmax = v if gmax is None else max(gmax, v)
            if ck.diag_min is not None:
                dv = float(np.asarray(ck.diag_min))
                ds = float(np.asarray(ck.diag_signed))
                min_diag = dv if min_diag is None else min(min_diag, dv)
                tiny = self._diag_threshold(scale)
                if self.driver == "cholesky" and ds <= 0.0:
                    flags.append({"kind": "nonpositive_diag",
                                  "phase": ck.phase, "step": ck.step,
                                  "value": ds})
                elif dv <= tiny:
                    flags.append({"kind": "small_pivot", "phase": ck.phase,
                                  "step": ck.step, "value": dv})
        growth = None
        if gmax is not None and scale:
            growth = gmax / scale
            if growth > self.growth_limit:
                worst = max((ck for ck in self._checks
                             if ck.maxabs is not None),
                            key=lambda ck: float(np.asarray(ck.maxabs)))
                flags.append({"kind": "growth", "phase": worst.phase,
                              "step": worst.step, "value": growth})
        doc = {"schema": HEALTH_SCHEMA, "driver": self.driver,
               "ok": not flags, "checks": len(self._checks), "flags": flags,
               "growth_estimate": growth, "scale": scale,
               "min_diag": min_diag,
               "failing_phase": flags[0]["phase"] if flags else None}
        self._report = doc
        if emit and not self._emitted:
            self._emitted = True
            self._emit(doc)
        return doc

    def _diag_threshold(self, scale) -> float:
        if self.diag_rtol is not None:
            rtol = self.diag_rtol
        else:
            rtol = 8.0 * (self._eps if self._eps is not None else 1e-7)
        return rtol * (scale if scale else 1.0)

    def _emit(self, doc: dict) -> None:
        from ..obs import metrics as _metrics
        from ..obs.tracer import active_tracer
        drv = doc["driver"] or "?"
        _metrics.inc("health_checks", doc["checks"], driver=drv)
        tr = active_tracer()
        for fl in doc["flags"]:
            _metrics.inc("health_flags", driver=drv, kind=fl["kind"],
                         phase=fl["phase"])
            if tr is not None:
                tr.instant(f"health:{fl['kind']}", driver=drv,
                           phase=fl["phase"], step=fl["step"],
                           value=fl["value"])
        _LAST[drv] = doc
        _LAST["_latest"] = doc


#: the most recent emitted report per driver (+ "_latest"); the
#: ``health=True`` convenience form lands here so callers who did not
#: keep the monitor can still read the outcome.
_LAST: dict = {}


def last_health_report(driver: str | None = None) -> dict | None:
    """The most recently emitted ``health_report/v1`` (per driver, or the
    latest overall with ``driver=None``)."""
    return _LAST.get(driver if driver is not None else "_latest")


def attach_health(driver: str, health, hook, scale_from=None):
    """Resolve a driver's ``health=`` argument into (hook', monitor).

    ``health`` may be a :class:`HealthMonitor` (caller-owned: read
    ``monitor.report()`` afterwards) or any truthy value (driver-internal
    monitor; the emitted report is retrievable via
    :func:`last_health_report`).  The returned hook fans ticks out to both
    the existing hook (timer / tracer channel / NULL_HOOK) and the
    monitor; with a falsy ``health`` the hook passes through untouched."""
    if not health:
        return hook, None
    mon = health if isinstance(health, HealthMonitor) else HealthMonitor()
    mon.begin(driver, scale_from=scale_from)
    if hook is NULL_HOOK or hook is None:
        return mon, mon
    return _Fanout((hook, mon)), mon


def factor_diag_info(op: str, factor) -> dict:
    """Structured singularity signal from a packed factor's diagonal.

    ``op``: ``'lu'`` (packed L\\U: the diagonal holds U's pivots;
    non-finite or numerically-zero -- ``|u_kk| <= k * eps * max|u|``, the
    floating-point image of an exactly-singular input, whose cancellation
    rarely survives pivoting bit-exactly -- == singular) or ``'hpd'``
    (Cholesky L/U factor: non-finite -- ``jnp.linalg.cholesky`` NaNs past
    the breakdown point -- or non-positive / numerically-zero real
    diagonal == not positive definite).  Returns::

        {"singular": bool, "diag_index": first offending index | None,
         "finite": bool}

    Engine-free (``get_diagonal`` is a pure storage reduction), so the
    signal is trustworthy even under fault injection."""
    from ..blas.level1 import get_diagonal
    d = np.asarray(get_diagonal(factor).local).ravel()
    finite = bool(np.isfinite(d).all())
    mag = np.abs(d[np.isfinite(d)])
    dmax = float(mag.max()) if mag.size else 0.0
    eps = float(np.finfo(d.dtype).eps) if np.issubdtype(d.dtype, np.inexact) \
        else 0.0
    tiny = max(d.size, 1) * eps * dmax
    if op == "lu":
        bad = ~np.isfinite(d) | (np.abs(d) <= tiny)
    else:
        bad = ~np.isfinite(d) | (np.real(d) <= tiny)
    idx = int(np.argmax(bad)) if bad.any() else None
    return {"singular": bool(bad.any()), "diag_index": idx, "finite": finite}
