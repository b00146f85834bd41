"""Tick-level tracing for the serving engine: structured spans on a bounded
ring buffer, exported as Chrome/Perfetto trace-event JSON, and mirrored onto
the ``jax.profiler`` timeline.

The :class:`Tracer` is host-side: every record is a plain tuple appended to
a ``collections.deque(maxlen=capacity)``, so memory is O(1) in run length
and a dropped-oldest counter keeps the loss visible.  Four record families
cover the engine's timeline:

  * **duration spans** — ``with tracer.span("gather", tid=lane): ...`` or
    the split form ``tok = tracer.begin("tick"); ...; tracer.end(tok)`` for
    spans that cross function boundaries (a pipelined tick is *begun* at
    issue and *ended* at drain, possibly several engine ticks later).
    ``tid`` is the track: the engine maps pipeline lanes to tracks so
    depth>=2 tick spans render side by side and a stall shows as a gap.
    While the tracer is enabled and a profiler session runs, each such
    span also opens and closes a ``jax.profiler.TraceAnnotation`` named
    ``hashmem/<name>``, so the session holds the engine's phases on the
    device trace's own clock.  ``complete()`` records a span after the
    fact (a compile that JAX reports once it has finished): ring only;
  * **counters** — ``tracer.counter("occupancy", v)``: per-tick counter
    tracks (occupancy, route-cap fill, rows activated, routed all_to_all
    element volume);
  * **instants** — ``tracer.instant("kill", rid=...)``: point events for
    request aborts and write-claim fence hits;
  * **async request lifecycle** — ``async_begin/async_end("queue", id=rid)``:
    submit→admit→complete slices (``request`` wrapping ``queue`` then
    ``service``) keyed by request id, so overlapping requests don't fight
    over one track.

Counters, instants and async slices are ring-only.

``export(path)`` (or ``to_events()``) emits the Chrome trace-event JSON
array format: span records become balanced ``B``/``E`` pairs replayed
through a per-track nesting sweep (timestamps monotonic per track, children
clamped inside parents), counters become ``C`` events, instants ``i``, and
request slices ``b``/``e`` async pairs — openable directly in Perfetto
(https://ui.perfetto.dev) or chrome://tracing, and machine-checkable with
``tools/trace_report.py``.

A disabled tracer (``Tracer(enabled=False)``, the engine's default via
``NULL_TRACER``) keeps every record method a single attribute check and
makes no annotation, so the untraced hot path pays one branch per call
site; ``benchmarks/serving_bench.py`` measures the *enabled* cost as the
``trace_overhead`` row, gated <=1.10x by ``tools/bench_check.py``.

:data:`COMPILES` counts XLA compiles and jaxpr traces for the whole process
through one ``jax.monitoring`` listener, always on; while a watched tracer
is enabled, each compile also lands in its ring as a ``compile`` span.
"""
from __future__ import annotations

import json
import time
import weakref
from collections import deque
from contextlib import contextmanager

from jax import monitoring
from jax.profiler import TraceAnnotation

# the documented span vocabulary (tools/trace_report.py groups by these;
# make trace-smoke asserts the core ones appear in a real mesh run)
SPAN_NAMES = (
    "tick",            # one engine tick, issue -> drain (lane track)
    "gather",          # per-slot op gather + claim/fence bookkeeping
    "route",           # two-pass routing capacity measurement (fused mesh)
    "probe", "delete", "insert",   # per-phase device-call dispatch
    "fused_tick",      # whole-tick megakernel dispatch (ONE shard_map)
    "writeback",       # drain: host materialization + result scatter
    "pipeline_stall",  # write-claim fence flush (depth>=2)
    "admit",           # completion sweep + slot refill
    "sample",          # throttled chain/rows-activated telemetry
    "grow",            # drain-time PR_ERROR repair, resize="rebuild"
    "split",           # same repair point, resize="extendible": per-group
                       # split/doubling — inline, NO pipeline flush
    "compact", "preload",
    "submit",          # ServingEngine.submit: validation, quota, placement
    "wait",            # drain: the host blocked on one phase's device results
    "telemetry",       # always-on MetricsCollector work (hot keys, request
                       # and tick records)
    "compile",         # an XLA compile or jaxpr trace (own track, ring only)
)

# profiler-side name of a duration span: ``hashmem/<span name>``, clear of
# the names other code annotates the same timeline with
ANNOTATION_PREFIX = "hashmem/"

_PID = 1                       # single-process engine: one trace pid
COMPILE_TID = 99               # the track of ``compile`` spans

# record kinds in the ring (field layout per kind)
_SPAN, _COUNTER, _INSTANT, _ABEGIN, _AEND = 0, 1, 2, 3, 4


class Tracer:
    """Bounded-ring span recorder with Chrome trace-event export.

    ``capacity`` bounds the ring (oldest records dropped, counted in
    ``self.dropped``); ``enabled=False`` turns every method into a cheap
    no-op (the shared :data:`NULL_TRACER` is exactly that).
    """

    def __init__(self, capacity: int = 1 << 16, enabled: bool = True):
        self.enabled = enabled
        self.capacity = capacity
        self._ring: deque = deque(maxlen=capacity)
        self._recorded = 0
        self._t0 = time.perf_counter()

    # -- clock -------------------------------------------------------------
    def now_us(self) -> float:
        """Microseconds since tracer creation (the trace timebase)."""
        return (time.perf_counter() - self._t0) * 1e6

    @property
    def dropped(self) -> int:
        return max(0, self._recorded - len(self._ring))

    def __len__(self) -> int:
        return len(self._ring)

    def _emit(self, rec: tuple):
        self._ring.append(rec)
        self._recorded += 1

    # -- spans -------------------------------------------------------------
    def begin(self, name: str, tid: int = 0, **args):
        """Open a span, and its profiler annotation while a profiler
        session runs; returns a token for :meth:`end`.  Use for spans
        that outlive the current scope (the engine's tick span stays open
        across pipelined ticks until drain)."""
        if not self.enabled:
            return None
        ann = None
        if TraceAnnotation.is_enabled():          # a profiler session runs
            ann = TraceAnnotation(ANNOTATION_PREFIX + name)
            ann.__enter__()
        return (name, tid, self.now_us(), args, ann)

    def end(self, token):
        """Close a span opened by :meth:`begin` (None tokens no-op).  The
        annotation closes even if the tracer was disabled in between; the
        ring records the span only while enabled."""
        if token is None:
            return
        name, tid, ts, args, ann = token
        dur = self.now_us() - ts
        if ann is not None:
            ann.__exit__(None, None, None)
        if self.enabled:
            self._emit((_SPAN, name, tid, ts, dur, args))

    def complete(self, name: str, ts: float, dur: float, tid: int = 0,
                 **args):
        """Record a finished span of ``dur`` us from ``ts`` (the
        :meth:`now_us` timebase) in the ring alone: it is over, so no
        annotation can be opened for it."""
        if not self.enabled:
            return
        self._emit((_SPAN, name, tid, ts, dur, args))

    @contextmanager
    def span(self, name: str, tid: int = 0, **args):
        tok = self.begin(name, tid, **args) if self.enabled else None
        try:
            yield
        finally:
            self.end(tok)

    # -- counters / instants ----------------------------------------------
    def counter(self, name: str, value, tid: int = 0):
        if not self.enabled:
            return
        self._emit((_COUNTER, name, tid, self.now_us(), float(value), None))

    def instant(self, name: str, tid: int = 0, **args):
        if not self.enabled:
            return
        self._emit((_INSTANT, name, tid, self.now_us(), 0.0, args))

    # -- async request lifecycle ------------------------------------------
    def async_begin(self, name: str, id: int, **args):
        if not self.enabled:
            return
        self._emit((_ABEGIN, name, id, self.now_us(), 0.0, args))

    def async_end(self, name: str, id: int, **args):
        if not self.enabled:
            return
        self._emit((_AEND, name, id, self.now_us(), 0.0, args))

    # -- export ------------------------------------------------------------
    def to_events(self) -> list:
        """Chrome trace-event dicts from the current ring contents.

        Span records are replayed per track through a nesting sweep —
        sorted by (start, -duration), a child whose interval extends past
        its parent (float jitter) is clamped inside — so the emitted
        ``B``/``E`` stream is balanced and timestamp-monotonic per track by
        construction, even after ring drops removed arbitrary records.
        Async ``b`` records are emitted with their matching ``e`` (an
        unmatched half — its partner aged out of the ring, or the request
        never completed — is dropped rather than exported unbalanced).
        """
        spans: dict = {}
        others: list = []
        abegins: dict = {}
        apairs: list = []
        for rec in self._ring:
            kind = rec[0]
            if kind == _SPAN:
                spans.setdefault(rec[2], []).append(rec)
            elif kind == _COUNTER:
                others.append({"name": rec[1], "ph": "C", "pid": _PID,
                               "tid": rec[2], "ts": rec[3],
                               "args": {"value": rec[4]}})
            elif kind == _INSTANT:
                others.append({"name": rec[1], "ph": "i", "s": "t",
                               "pid": _PID, "tid": rec[2], "ts": rec[3],
                               "args": rec[5] or {}})
            elif kind == _ABEGIN:
                abegins[(rec[1], rec[2])] = rec
            else:
                b = abegins.pop((rec[1], rec[2]), None)
                if b is not None:
                    apairs.append((b, rec))
        events: list = []
        for tid, recs in spans.items():
            events.extend(self._sweep_track(tid, recs))
        for b, e in apairs:
            base = {"cat": "request", "name": b[1], "id": b[2], "pid": _PID}
            events.append({**base, "ph": "b", "ts": b[3], "args": b[5] or {}})
            events.append({**base, "ph": "e", "ts": e[3], "args": e[5] or {}})
        events.extend(others)
        events.sort(key=lambda ev: ev["ts"])       # stable: keeps B/E order
        if not events:
            return []
        meta = [{"name": "process_name", "ph": "M", "pid": _PID,
                 "args": {"name": "ServingEngine"}}]
        for tid in sorted(spans):
            track = "compile" if tid == COMPILE_TID else f"lane {tid}"
            meta.append({"name": "thread_name", "ph": "M", "pid": _PID,
                         "tid": tid, "args": {"name": track}})
        return meta + events

    @staticmethod
    def _sweep_track(tid: int, recs: list) -> list:
        """One track's span records -> balanced, monotonic B/E events."""
        recs = sorted(recs, key=lambda r: (r[3], -r[4]))
        out: list = []
        stack: list = []                 # (name, end_ts)
        for _, name, _, ts, dur, args in recs:
            while stack and stack[-1][1] <= ts:
                n, e = stack.pop()
                out.append({"name": n, "ph": "E", "pid": _PID, "tid": tid,
                            "ts": e})
            end = ts + dur
            if stack and end > stack[-1][1]:
                end = stack[-1][1]       # clamp float jitter inside parent
            out.append({"name": name, "ph": "B", "pid": _PID, "tid": tid,
                        "ts": ts, "args": args or {}})
            stack.append((name, end))
        while stack:
            n, e = stack.pop()
            out.append({"name": n, "ph": "E", "pid": _PID, "tid": tid,
                        "ts": e})
        return out

    def export(self, path: str, **metadata) -> int:
        """Write the trace-event JSON file; returns the event count."""
        events = self.to_events()
        doc = {"traceEvents": events, "displayTimeUnit": "ms",
               "otherData": {"recorded": self._recorded,
                             "dropped": self.dropped, **metadata}}
        with open(path, "w") as f:
            json.dump(doc, f)
        return len(events)


#: shared disabled tracer — the engine's default, so call sites never
#: branch on None (every record method is one ``self.enabled`` check)
NULL_TRACER = Tracer(capacity=1, enabled=False)


class CompileCounter:
    """Counts of XLA backend compiles and jaxpr traces, and their seconds,
    from ``jax.monitoring``'s duration events.  One listener serves the
    process (the events are process-wide); it is registered by the first
    :meth:`watch`.  A compile that hits JAX's persistent cache still counts:
    the event times the lookup too.  Each watched tracer that is enabled
    when a compile ends gets a ``compile`` span on its own track."""

    EVENTS = {"/jax/core/compile/backend_compile_duration": "compile",
              "/jax/core/compile/jaxpr_trace_duration": "trace"}

    def __init__(self):
        self.counts = {"compiles": 0, "compile_s": 0.0,
                       "traces": 0, "trace_s": 0.0}
        self._tracers: weakref.WeakSet = weakref.WeakSet()
        self._listening = False

    def watch(self, tracer: Tracer):
        """Start counting, and give ``tracer`` a span for each compile."""
        if not self._listening:
            monitoring.register_event_duration_secs_listener(self._on_event)
            self._listening = True
        if tracer is not NULL_TRACER:
            self._tracers.add(tracer)

    def _on_event(self, event: str, seconds: float, **kwargs):
        kind = self.EVENTS.get(event)
        if kind is None:
            return
        self.counts[kind + "s"] += 1
        self.counts[kind + "_s"] += seconds
        for tr in list(self._tracers):
            if tr.enabled:
                dur = seconds * 1e6
                tr.complete("compile", tr.now_us() - dur, dur, COMPILE_TID,
                            kind=kind, fn=kwargs.get("fun_name", ""))

    def snapshot(self) -> dict:
        return dict(self.counts)


#: the process's compile counter (``ServingEngine.stats()["compiles"]``
#: reports its counts since the engine was built)
COMPILES = CompileCounter()
