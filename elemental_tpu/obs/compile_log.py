"""The compile log: what JAX traced, lowered and compiled, and for how long.

The half of a program's life no span or counter of this package saw
(ISSUE 39): ``jax.monitoring`` reports every trace of a jitted function's
Python, every lowering to MLIR and every backend compile (XLA compiling,
or the persistent cache handing the executable back), each with the
function's name and its start and end on ``time.time()``.  :func:`install`
registers the process's one :data:`LOG` with it, once, where the
persistent cache is turned on (``core/compile_cache.py``): no knob, no
keyword.  A listener runs only when JAX traces, lowers or compiles, so
nothing here is on a solve's path.

Three stages (:data:`STAGES`): ``trace`` (the library's own Python, the
unrolled loops), ``lower`` (jaxpr to MLIR), ``backend`` (compile, or
cache load).  **Spans nest and the seconds are SELF time**: the outer
trace of a solve holds the traces of every inner ``jit`` (thousands of
``add``, ``less``, ...), a lowering holds traces of its own, so a plain
sum of durations counts those instants twice.  JAX announces a span's
start as well as its end, so the log keeps the spans open on each thread
as a stack, and a span's self time is its duration less that of the spans
directly inside it: every instant belongs to the innermost span open on
its thread.

What it feeds:

  * :meth:`CompileLog.totals` -- self seconds and records per stage and
    the cache's requests, hits and misses, since the process began
    (``benchmark/setup_parts.py`` splits ``setup_s`` by them);
  * the CURRENT metrics registry -- ``compile_seconds{stage}`` (self
    time), ``compile_requests`` (backend compiles that asked the
    persistent cache), ``compile_cache_hits``, ``compile_cache_misses``
    (asked, and compiled all the same);
  * :attr:`CompileLog.records` -- a bounded ring of the OUTERMOST spans
    (name, stage, start, end, thread, hit or miss), each with the count
    of the spans inside it, which it stands for: thousands of 0.1 ms
    children cannot push the three 10 s parents out;
  * the active :class:`~elemental_tpu.obs.tracer.Tracer`, if any -- every
    span, nested ones too, as a ``compile/<stage>`` span under the span
    open on that thread, on the tracer's clock (its epoch anchor).
"""
from __future__ import annotations

import collections
import dataclasses
import threading

from jax import monitoring

from . import metrics as _metrics
from . import tracer as _tracer

#: JAX's event -> stage
STAGES = {"/jax/core/compile/jaxpr_trace_duration": "trace",
          "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
          "/jax/core/compile/backend_compile_duration": "backend"}

#: the cache's event -> (total it counts in, what it says of the open
#: ``backend`` span, counter it ticks): a request is a miss until a hit
_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache":
        ("requests", "miss", "compile_requests"),
    "/jax/compilation_cache/cache_hits":
        ("hits", "hit", "compile_cache_hits")}
#: a hit's duration event -> the record's field
_HIT_SECONDS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval_s",
    "/jax/compilation_cache/compile_time_saved_sec": "compile_saved_s"}

#: outermost spans the ring keeps
RING = 1024


@dataclasses.dataclass
class CompileRecord:
    """One span of a program's compile lifecycle."""
    stage: str                   # "trace" | "lower" | "backend"
    fun_name: str                # "bench_solve"; a module: "jit(bench_solve)"
    start: float                 # time.time()
    end: float
    thread: int
    #: duration less that of the spans directly inside it
    self_s: float = 0.0
    #: "hit" | "miss" (the persistent cache was asked), "" (it was not)
    cache: str = ""
    #: spans inside this one (at any depth), which it stands for in the ring
    covered: int = 0
    #: a hit's seconds in the cache's read, and the compile seconds it saved
    cache_retrieval_s: float | None = None
    compile_saved_s: float | None = None
    #: seconds of the spans directly inside (what ``self_s`` leaves out)
    inside_s: float = 0.0

    @property
    def seconds(self) -> float:
        return self.end - self.start


class CompileLog:
    """Open spans per thread, self-time totals, and the ring."""

    def __init__(self, ring: int = RING):
        self.records: collections.deque = collections.deque(maxlen=ring)
        self._seconds = dict.fromkeys(STAGES.values(), 0.0)
        self._count = dict.fromkeys(STAGES.values(), 0)
        self._cache = {"requests": 0, "hits": 0, "misses": 0}
        self._open: dict = {}             # thread ident -> [open records]
        self._lock = threading.Lock()

    # ---- the four listeners (jax.monitoring's signatures) -------------
    def on_start(self, event, start, fun_name="", **_kw):
        """A span opens (``record_scalar``: its start time)."""
        stage = STAGES.get(event)
        if stage is None:
            return
        ident = threading.get_ident()
        rec = CompileRecord(stage, str(fun_name), start, start, ident)
        with self._lock:
            self._open.setdefault(ident, []).append(rec)

    def on_span(self, event, start, end, fun_name="", **_kw):
        """A span closes (``record_event_time_span``): children first."""
        stage = STAGES.get(event)
        if stage is None:
            return
        ident = threading.get_ident()
        with self._lock:
            stack = self._open.get(ident, ())
            # the span that opened at this instant; what was opened above
            # it and never closed is dropped, a span the log never saw
            # open is taken as it comes
            at = next((i for i in range(len(stack) - 1, -1, -1)
                       if stack[i].start == start
                       and stack[i].stage == stage), None)
            if at is None:
                rec = CompileRecord(stage, str(fun_name), start, end, ident)
            else:
                rec = stack[at]
                del stack[at:]
                rec.end = end
            rec.self_s = max(rec.seconds - rec.inside_s, 0.0)
            parent = stack[-1] if stack else None
            if parent is not None:
                parent.inside_s += rec.seconds
                parent.covered += rec.covered + 1
            else:                       # outermost: nothing open here now
                self.records.append(rec)
                self._open.pop(ident, None)
            self._seconds[stage] += rec.self_s
            self._count[stage] += 1
            missed = stage == "backend" and rec.cache == "miss"
            self._cache["misses"] += missed
        _metrics.inc("compile_seconds", rec.self_s, stage=stage)
        if missed:
            _metrics.inc("compile_cache_misses")
        tracer = _tracer.active_tracer()
        if tracer is not None:
            tracer.compile_span(rec, nested=len(stack), parent=parent)

    def on_event(self, event, **_kw):
        """The persistent cache was asked / had the executable: inside the
        ``backend`` span open on this thread."""
        found = _CACHE_EVENTS.get(event)
        if found is None:
            return
        key, outcome, counter = found
        with self._lock:
            self._cache[key] += 1
            rec = self._open_backend()
            if rec is not None:
                rec.cache = outcome
        _metrics.inc(counter)

    def on_duration(self, event, seconds, **_kw):
        """A hit's read time and the compile time it saved."""
        field = _HIT_SECONDS.get(event)
        if field is None:
            return
        with self._lock:
            rec = self._open_backend()
            if rec is not None:
                setattr(rec, field, seconds)

    def _open_backend(self):
        stack = self._open.get(threading.get_ident())
        if stack and stack[-1].stage == "backend":
            return stack[-1]
        return None

    # ---- reads --------------------------------------------------------
    def totals(self) -> dict:
        """``{"seconds": {stage: self seconds}, "records": {stage: n},
        "requests", "hits", "misses"}`` since the log began."""
        with self._lock:
            return {"seconds": dict(self._seconds),
                    "records": dict(self._count), **self._cache}


#: the process's log, what :func:`install` feeds
LOG = CompileLog()

_installed = False
_install_lock = threading.Lock()


def install() -> CompileLog:
    """Register :data:`LOG` with ``jax.monitoring``; a second call
    registers nothing."""
    global _installed
    with _install_lock:
        if not _installed:
            monitoring.register_scalar_listener(LOG.on_start)
            monitoring.register_event_time_span_listener(LOG.on_span)
            monitoring.register_event_listener(LOG.on_event)
            monitoring.register_event_duration_secs_listener(LOG.on_duration)
            _installed = True
    return LOG
