"""Runtime span tracer: nested spans, driver phase hooks, collective events.

The structural half of the observability subsystem (ISSUE 5).  Every
driver marks its phases ONE way, the scoped form of the hook that
:func:`phase_hook` hands it::

    with tm.phase("panel", k) as ph:
        ...the phase's work...
        ph.done(L21_vc)

That form works in both modes.  It always enters
``jax.named_scope("k<step>/<phase>")`` (trace-time metadata only), so
under ``jax.jit`` -- where the hook is :data:`NULL_HOOK` -- the phase
still names its ops in the compiled program and a device trace can be
split by phase (grammar in :mod:`elemental_tpu.obs`).  When a
``PhaseTimer`` or an active :class:`Tracer` stands behind the hook,
``ph.done(*arrays)`` makes the block's exit also ``tick`` the hook: the
host-clock protocol below, for EAGER runs.  A block left without
``done`` (a naming-only scope, a ``continue``, an exception) ticks
nothing.

A :class:`Tracer` records three kinds of evidence from ONE eager run:

  * explicit spans -- ``with tracer.span(name, sync=outputs, **attrs):``
    context-manager blocks that nest via a stack; ``sync`` takes the
    phase's output arrays and the span closes only after
    ``jax.block_until_ready`` on them, so the recorded wall-clock is
    honest under jax's async dispatch;
  * phase records -- the driver hooks.  Every tuned driver (``cholesky``,
    ``lu``, ``qr``, ``gemm``, ``trsm``, ``herk``) arms its hook
    (``start()``) and closes each phase through the scoped form, whose
    exit calls ``tick(phase, step, *arrays)``; a tracer-backed
    :class:`_TickChannel` turns those ticks into (driver, phase, step,
    t0, t1) records, from which the exporter synthesizes the driver ->
    step -> phase span nesting.  ``tick`` blocks on the phase's outputs
    and charges the time since the previous tick, as
    :class:`~elemental_tpu.obs.phase_timer.PhaseTimer` always did;
  * collective events -- while a tracer is ACTIVE (``with tracer:``), it
    registers an observer on the redistribution engine's trace hook, so
    every public ``redistribute``/``panel_spread`` entry lands as an
    instant event carrying src/dst distributions, global shape, dtype,
    and a ring-model byte estimate, attributed to the innermost open
    span / most recent driver.

Activation (``with tracer:``) also makes the tracer the process-current
one, so :func:`phase_hook` -- the single line each driver runs at entry
-- routes the driver's ticks here without any driver-level plumbing.
The tracer's clock is an EAGER-mode tool: under ``jax.jit`` nothing is
attached, the driver fuses into one program, and the phases leave their
names (the scopes above) instead of host times.

Metrics: unless constructed with ``metrics=False``, every phase record
feeds a ``phase_seconds{driver,phase}`` histogram and every collective
event bumps ``redist_calls{label}`` / ``redist_bytes{label}`` counters
on the CURRENT :mod:`.metrics` registry; :func:`phase_hook` additionally
counts ``op_calls{op}`` per driver entry (Python-entry counts, the same
caveat as ``engine.REDIST_COUNTS``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading
import time

import jax
import numpy as np

from . import metrics as _metrics

TRACE_SCHEMA = "obs_trace/v1"


@dataclasses.dataclass
class Span:
    """One explicit (context-manager) span."""
    name: str
    t0: float
    t1: float | None
    depth: int
    attrs: dict
    #: originating thread (0 = unattributed/legacy); the exporter keys
    #: Chrome-trace tracks by this so fleet worker spans don't collide
    thread: int = 0
    thread_name: str = ""


@dataclasses.dataclass
class PhaseRecord:
    """One driver phase interval reconstructed from a tick."""
    driver: str
    phase: str
    step: int
    t0: float
    t1: float
    call: int                    # driver-invocation ordinal (channel id)
    thread: int = 0
    thread_name: str = ""

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


@dataclasses.dataclass
class InstantEvent:
    """One generic instant event (e.g. a resilience health flag)."""
    t: float
    name: str
    attrs: dict
    thread: int = 0
    thread_name: str = ""


@dataclasses.dataclass
class CommEvent:
    """One public redistribute/panel_spread entry observed at runtime."""
    t: float
    kind: str                    # "redistribute" | "panel_spread" | "row_permute"
    label: str                   # "[MC,MR]->[STAR,STAR]" | "panel_spread"
    gshape: tuple
    dtype: str
    bytes: int                   # ring-model estimate at the LOGICAL dtype
    span: str | None             # innermost open explicit span
    driver: str | None           # most recent driver channel
    #: dtype/bytes actually on the wire: == dtype/bytes unless the entry
    #: ran under a ``comm_precision`` mode (ISSUE 8), where the payload
    #: is bfloat16/int8 and wire_bytes shows the 2-4x drop
    wire_dtype: str = ""
    wire_bytes: int = 0
    #: route the engine resolved (ISSUE 12): "chain" | "direct" |
    #: "storage" (row-permute fast path); "" for pre-path entries
    path: str = ""
    #: collective rounds of the resolved route (-1 = engine didn't price)
    rounds: int = -1
    #: the engine's exact ring-model pricing of the resolved route at the
    #: wire dtype (-1 = not computed) -- finer than the coarse ``bytes``/
    #: ``wire_bytes`` estimate, and the per-round byte record of the path
    engine_wire_bytes: int = -1
    thread: int = 0
    thread_name: str = ""


def ring_bytes(gshape, dtype, grid_shape) -> int:
    """Ring-model per-device byte estimate for moving a ``gshape`` matrix
    across a ``grid_shape`` mesh: each device receives the payload minus
    its own shard, ``payload * (p - 1) / p`` (0 on a 1x1 grid -- no
    collective executes).  The jaxpr-level analyzer
    (``analysis.jaxpr_walk.estimate_bytes``) refines this per collective;
    at the public-entry granularity recorded here the single formula is
    the honest common denominator."""
    try:
        itemsize = np.dtype(dtype).itemsize
    except TypeError:
        itemsize = 4
    payload = itemsize
    for d in gshape:
        payload *= int(d)
    p = 1
    for d in grid_shape:
        p *= int(d)
    if p <= 1:
        return 0
    return payload * (p - 1) // p


def scoped(name: str):
    """Decorator: run the function inside ``jax.named_scope(name)`` (a
    public driver's ``el.<driver>`` scope).  The scope is looked up at
    call time, and costs nothing outside a trace."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return wrapper
    return deco


#: the three parts of an exchange, the segments that may stand under an
#: ``el.redist.<name>`` scope: the local ops that feed its collective, the
#: explicit collective itself, the local ops after it (grammar in
#: :mod:`elemental_tpu.obs`)
REDIST_PARTS = ("pack", "wire", "unpack")


def redist_part(part: str):
    """``jax.named_scope(part)`` for one of :data:`REDIST_PARTS`, opened by
    the redistribution engine's primitives where the work is emitted.  Like
    every scope it is looked up at call time and names ops only."""
    if part not in REDIST_PARTS:
        raise ValueError(f"part must be one of {REDIST_PARTS}, got {part!r}")
    return jax.named_scope(part)


class _Phase:
    """One ``with hook.phase(phase, step) as ph:`` block."""
    __slots__ = ("_hook", "_phase", "_step", "_arrays", "_scope")

    def __init__(self, hook, phase, step):
        self._hook = hook
        self._phase = phase
        self._step = step
        self._arrays = None
        self._scope = None

    def done(self, *arrays):
        """The phase is complete and ``arrays`` are its outputs: on the
        block's exit the hook ticks (and blocks on them, when it times)."""
        self._arrays = arrays

    def __enter__(self):
        self._scope = jax.named_scope(
            f"k{int(self._step):02d}/{self._phase}")
        self._scope.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        self._scope.__exit__(exc_type, exc, tb)
        if exc_type is None and self._arrays is not None:
            self._hook.tick(self._phase, self._step, *self._arrays)
        return False


class _AnnotatedPhase(_Phase):
    """The scoped form behind an active :class:`Tracer`: the block is
    also a ``jax.profiler.TraceAnnotation`` ``<driver>/k<step>/<phase>``,
    so with the profiler running the phase stands in the trace's host
    plane beside the device's ops."""
    __slots__ = ("_annotation",)

    def __init__(self, hook, driver, phase, step):
        super().__init__(hook, phase, step)
        self._annotation = jax.profiler.TraceAnnotation(
            f"{driver}/k{int(step):02d}/{phase}")

    def __enter__(self):
        self._annotation.__enter__()
        return super().__enter__()

    def __exit__(self, exc_type, exc, tb):
        try:
            return super().__exit__(exc_type, exc, tb)
        finally:
            self._annotation.__exit__(exc_type, exc, tb)


class PhaseHook:
    """What every hook a driver may hold offers: ``start()``, and the
    scoped form ``phase(phase, step)``.  ``tick`` is what that form calls
    on exit (and what forwarding hooks pass on), not a second way for a
    driver to mark a phase."""
    __slots__ = ()

    def start(self):
        pass

    def tick(self, phase, step, *arrays):
        pass

    def phase(self, phase, step) -> _Phase:
        """Scope the ops of ``(phase, step)``: ``k<step>/<phase>`` in the
        compiled program's op names; with ``done(*arrays)`` inside the
        block, its exit ticks this hook."""
        return _Phase(self, phase, step)


class NullHook(PhaseHook):
    """Stand-in when nothing times the run (the hook ``jit`` gets): the
    scoped form still names the phase, and its tick does nothing."""
    __slots__ = ()


NULL_HOOK = NullHook()


class _TickChannel(PhaseHook):
    """One driver invocation's tick stream (PhaseTimer protocol)."""
    __slots__ = ("tracer", "driver", "attrs", "call", "_t")

    def __init__(self, tracer: "Tracer", driver: str, call: int, attrs: dict):
        self.tracer = tracer
        self.driver = driver
        self.attrs = attrs
        self.call = call
        self._t = None

    def start(self):
        """(Re)arm the clock at a driver's entry."""
        self._t = self.tracer.clock()

    def phase(self, phase, step) -> _Phase:
        return _AnnotatedPhase(self, self.driver, phase, step)

    def tick(self, phase, step, *arrays):
        """Block on ``arrays`` and close the [previous-tick, now] phase."""
        if arrays:
            jax.block_until_ready(arrays)
        now = self.tracer.clock()
        t0 = self._t if self._t is not None else now
        self.tracer._add_phase(self.driver, str(phase), int(step), t0, now,
                               self.call)
        self._t = now


class _Fanout(PhaseHook):
    """Tick fan-out: an explicit PhaseTimer AND the active tracer both see
    every tick (the first hook's block_until_ready makes the second ~free)."""
    __slots__ = ("hooks",)

    def __init__(self, hooks):
        self.hooks = tuple(hooks)

    def start(self):
        for h in self.hooks:
            h.start()

    def phase(self, phase, step) -> _Phase:
        for h in self.hooks:
            if isinstance(h, _TickChannel):
                return _AnnotatedPhase(self, h.driver, phase, step)
        return _Phase(self, phase, step)

    def tick(self, phase, step, *arrays):
        for h in self.hooks:
            h.tick(phase, step, *arrays)


_ACTIVE: "Tracer | None" = None


def active_tracer() -> "Tracer | None":
    """The tracer currently activated via ``with tracer:``, if any."""
    return _ACTIVE


class Tracer:
    """Collects spans, driver phase records, and collective events.

    Thread-safe (ISSUE 20 satellite): fleet GridWorker threads record
    spans/phases/instants concurrently with the submitting thread.  The
    shared record lists append under one lock; span NESTING state (the
    open-span stack and the most-recent-driver attribution) is
    thread-local, so each thread nests independently and the exporter
    can key tracks by the recorded originating thread.
    """

    def __init__(self, metrics: bool = True, clock=time.perf_counter):
        self.clock = clock
        self.spans: list[Span] = []
        self.phases: list[PhaseRecord] = []
        self.comms: list[CommEvent] = []
        self.instants: list[InstantEvent] = []
        self.home_thread = threading.get_ident()
        self._lock = threading.Lock()
        self._tls = threading.local()
        self._metrics = metrics
        self._ncalls = 0
        self._prev_active: Tracer | None = None
        self._unobserve = None
        #: ``(time.time_ns(), clock())`` read together at activation: what
        #: puts these spans, JAX's compile spans and a profiler trace (all
        #: on the epoch clock) on one time line
        self.epoch_anchor: tuple | None = None

    def _thread_stack(self) -> list:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    @property
    def _cur_driver(self):
        return getattr(self._tls, "driver", None)

    @_cur_driver.setter
    def _cur_driver(self, driver):
        self._tls.driver = driver

    @staticmethod
    def _whoami() -> tuple:
        return threading.get_ident(), threading.current_thread().name

    # ---- explicit spans ---------------------------------------------
    @contextlib.contextmanager
    def span(self, name: str, sync=None, **attrs):
        """Open a nested span; if ``sync`` is given (arrays / pytree), the
        span blocks on it before closing so the duration is honest."""
        stack = self._thread_stack()
        ident, tname = self._whoami()
        s = Span(name=str(name), t0=self.clock(), t1=None,
                 depth=len(stack), attrs=dict(attrs), thread=ident,
                 thread_name=tname)
        with self._lock:
            self.spans.append(s)
        stack.append(s)
        try:
            with jax.profiler.TraceAnnotation(s.name):
                yield s
        finally:
            if sync is not None:
                jax.block_until_ready(sync)
            s.t1 = self.clock()
            stack.pop()

    def compile_span(self, rec, nested: int = 0, parent=None) -> None:
        """One record of the compile log (:mod:`.compile_log`) as a span
        ``compile/<stage>``: child of the compile span it is nested in,
        else of the span open on this thread; its epoch times moved onto
        this tracer's clock by the anchor."""
        stack = self._thread_stack()
        epoch_ns, clock_s = self.epoch_anchor
        shift = clock_s - epoch_ns * 1e-9
        ident, tname = self._whoami()
        attrs = {"fun_name": rec.fun_name, "cache": rec.cache,
                 "self_s": rec.self_s}
        if parent is not None:
            attrs["parent"] = f"compile/{parent.stage}"
        elif stack:
            attrs["parent"] = stack[-1].name
        s = Span(name=f"compile/{rec.stage}", t0=rec.start + shift,
                 t1=rec.end + shift, depth=len(stack) + nested, attrs=attrs,
                 thread=ident, thread_name=tname)
        with self._lock:
            self.spans.append(s)

    # ---- driver tick channels ---------------------------------------
    def channel(self, driver: str, **attrs) -> _TickChannel:
        """A fresh tick channel; one per driver invocation."""
        with self._lock:
            self._ncalls += 1
            call = self._ncalls
        self._cur_driver = driver
        return _TickChannel(self, driver, call, attrs)

    def _add_phase(self, driver, phase, step, t0, t1, call):
        ident, tname = self._whoami()
        rec = PhaseRecord(driver, phase, step, t0, t1, call,
                          thread=ident, thread_name=tname)
        with self._lock:
            self.phases.append(rec)
        self._cur_driver = driver
        if self._metrics:
            _metrics.observe("phase_seconds", t1 - t0, driver=driver,
                             phase=phase)

    # ---- generic instant events -------------------------------------
    def instant(self, name: str, **attrs) -> None:
        """Record a zero-duration event (rendered on an ``events`` track
        by the Chrome-trace exporter).  The resilience health guards use
        this to surface ``health:<kind>`` flags inline with the phase
        spans of the run that produced them; request lifecycle marks use
        it with a ``flow=`` attr, which the exporter links into
        Chrome-trace flow events (``ph: s/t/f``)."""
        ident, tname = self._whoami()
        ev = InstantEvent(t=self.clock(), name=str(name),
                          attrs=dict(attrs), thread=ident,
                          thread_name=tname)
        with self._lock:
            self.instants.append(ev)

    # ---- engine observer --------------------------------------------
    def _on_redist(self, rec) -> None:
        grid_shape = getattr(rec, "grid_shape", ())
        nbytes = ring_bytes(rec.gshape, rec.dtype, grid_shape)
        wire = getattr(rec, "wire_dtype", "") or rec.dtype
        wbytes = nbytes if wire == rec.dtype \
            else ring_bytes(rec.gshape, wire, grid_shape)
        stack = self._thread_stack()
        ident, tname = self._whoami()
        ev = CommEvent(
            t=self.clock(), kind=rec.kind, label=rec.label,
            gshape=tuple(rec.gshape), dtype=rec.dtype, bytes=nbytes,
            span=stack[-1].name if stack else None,
            driver=self._cur_driver, wire_dtype=wire, wire_bytes=wbytes,
            path=str(getattr(rec, "path", "") or ""),
            rounds=int(getattr(rec, "rounds", -1)),
            engine_wire_bytes=int(getattr(rec, "wire_bytes", -1)),
            thread=ident, thread_name=tname)
        with self._lock:
            self.comms.append(ev)
        if self._metrics:
            _metrics.inc("redist_calls", label=rec.label)
            _metrics.inc("redist_bytes", nbytes, label=rec.label)
            _metrics.inc("redist_wire_bytes", wbytes, label=rec.label)
            # byte-family histogram (per-family ladder, ISSUE 20): the
            # wire-byte distribution per entry, not just the total
            _metrics.observe("redist_event_bytes", wbytes,
                             label=rec.label)

    # ---- activation --------------------------------------------------
    def __enter__(self) -> "Tracer":
        global _ACTIVE
        from ..redist.engine import add_redist_observer
        self._prev_active = _ACTIVE
        if self.epoch_anchor is None:
            self.epoch_anchor = (time.time_ns(), self.clock())
        _ACTIVE = self
        self._unobserve = add_redist_observer(self._on_redist)
        return self

    def __exit__(self, *exc) -> None:
        global _ACTIVE
        _ACTIVE = self._prev_active
        self._prev_active = None
        if self._unobserve is not None:
            self._unobserve()
            self._unobserve = None

    # ---- aggregation -------------------------------------------------
    def redist_counts(self) -> dict:
        """{label: count} over the recorded collective events -- the
        runtime twin of a ``comm_plan/v1`` document's ``redistributes``
        table (tests cross-check the two against the goldens).  Storage
        -level ``row_permute`` entries are excluded to match: GSPMD plans
        their motion, so the goldens pin no explicit rounds for them (the
        byte totals below still count their wire traffic)."""
        out: dict = {}
        for ev in self.comms:
            if ev.kind == "row_permute":
                continue
            out[ev.label] = out.get(ev.label, 0) + 1
        return dict(sorted(out.items()))

    def redist_bytes_total(self) -> int:
        return sum(ev.bytes for ev in self.comms)

    def redist_wire_bytes_total(self) -> int:
        """Total estimated bytes actually moved on the wire -- equals
        :meth:`redist_bytes_total` unless some entries ran under a
        ``comm_precision`` mode (the quantized-collective win, measurable
        end-to-end here)."""
        return sum(ev.wire_bytes for ev in self.comms)

    def phase_totals(self) -> dict:
        """{driver: {phase: seconds}} aggregated over all records."""
        out: dict = {}
        for r in self.phases:
            d = out.setdefault(r.driver, {})
            d[r.phase] = d.get(r.phase, 0.0) + r.seconds
        return out

    def driver_calls(self) -> list:
        """[(call id, driver, t0, t1, steps)] synthesized from phase
        records -- one entry per driver invocation (tick channel)."""
        agg: dict = {}
        for r in self.phases:
            cur = agg.get(r.call)
            if cur is None:
                agg[r.call] = [r.call, r.driver, r.t0, r.t1, {r.step}]
            else:
                cur[2] = min(cur[2], r.t0)
                cur[3] = max(cur[3], r.t1)
                cur[4].add(r.step)
        return [tuple(v[:4]) + (sorted(v[4]),)
                for _, v in sorted(agg.items())]


def phase_hook(driver: str, timer=None, **attrs):
    """The one-line driver integration: resolve this invocation's tick
    hook.  Counts the invocation (``op_calls{op=driver}`` on the current
    metrics registry), then returns

      * the explicit ``timer`` when no tracer is active (classic
        PhaseTimer usage, unchanged; a caller's own object that has only
        ``start()`` and ``tick()`` is wrapped so that it, too, offers the
        scoped form),
      * the active tracer's fresh channel when one is activated,
      * a fan-out over both when both are present,
      * the shared :data:`NULL_HOOK` when neither -- what ``jit`` gets:
        the scoped form names the phases and times nothing.
    """
    _metrics.inc("op_calls", op=driver)
    if timer is not None and not hasattr(timer, "phase"):
        timer = _Fanout((timer,))     # a bare start()/tick() object
    tr = _ACTIVE
    if tr is None:
        return timer if timer is not None else NULL_HOOK
    chan = tr.channel(driver, **attrs)
    chan.start()
    if timer is None:
        return chan
    return _Fanout((timer, chan))
