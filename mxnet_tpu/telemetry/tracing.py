"""Structured span tracing — request/step-scoped causal timelines (ISSUE 4).

The metric registry answers *how much*; this module answers *where one unit
of work spent its time*.  A **trace** is one request or one train step; its
**spans** are the stages (``queue → classify → assemble → execute`` for
serving, ``forward_backward / update / data_wait`` for training), each
stamped with the trace id so a 504-reaped request or a slow fused step is
visible as a causal timeline even when its lifecycle crosses threads
(serving ``submit`` → device loop).

Design:

- the switch is the profiler session: a span is recorded while a
  ``jax.profiler`` trace is being taken (``TraceAnnotation.is_enabled()``,
  checked first: tens of nanoseconds) or ``MXNET_TRACE`` is set
  (docs/ENV_VARS.md, a span-only run).  Neither ⇒ ``start_trace``/``span``
  return the shared ``NULL_SPAN`` singleton — no tracer object, no buffer,
  no file (tested like the ``test_noop_guard_*`` family);
- while a session is live a span entered as a context manager also opens a
  ``jax.profiler.TraceAnnotation(name, trace=<id>, **attrs)``: it lands on
  the xplane's host plane, on the clock of the device's ``XLA Ops`` lines,
  nested by containment in whatever annotation the caller holds.  Attrs
  and ``count()``ers that change inside the span are appended when it
  closes.  Spans closed by an explicit ``finish()`` (they may end on
  another thread) stay in the ring only;
- sampling is per trace root: ``MXNET_TRACE_SAMPLE`` (0..1) keeps that
  fraction of traces via deterministic systematic sampling, and an
  unsampled root propagates nothing — child ``span()`` calls under it are
  ``NULL_SPAN`` too.  Under a live session every root is kept;
- finished spans land in a bounded in-memory ring (``MXNET_TRACE_BUFFER``
  spans, oldest evicted) — tracing a long run can never grow memory without
  limit; ``snapshot()`` is what an in-process reader (the benchmark's
  per-layer metrics) reads;
- ``count(name)`` adds to a counter attr of the innermost span, so a sum
  over one root says how many happened in that step and under which span;
- ``record(name, start_s, end_s)`` puts a span that ended elsewhere into
  the ring, its ``time.time()`` stamps moved onto this module's clock:
  ``compile_cache`` records each of JAX's compile stages so
  (``compile.trace`` / ``compile.lower`` / ``compile.backend``, attr
  ``fun_name``), and a compile inside a traced step names its program;
- ``export()`` writes Chrome-trace/Perfetto JSON: ``ph:"X"`` duration
  events plus ``ph:"s"``/``ph:"f"`` flow events linking a trace's spans
  across threads, thread-name metadata, and a ``clock_sync`` record
  (unix time ↔ trace timestamp) so ``tools/trace_merge.py`` can merge the
  host spans with an ``mx.profiler`` / XLA profiler trace on one timeline.
  Timestamps share ``mx.profiler``'s perf_counter epoch, so a profiler dump
  from the same process needs no offset at all.

Cross-thread propagation: the producing thread captures ``span.context()``
and hands the ``SpanContext`` to the consumer; ``span(name, parent=ctx)``
on the consumer thread creates a flow-linked child — the ``"s"`` anchor
(stamped with the producer's track and capture time) and the ``"f"`` bind
are both emitted at bind time, so a captured-but-never-consumed context
leaves no unmatched flow event behind.  Long-lived
cross-thread spans (a serving request's ``queue`` time) use explicit
``finish()`` instead of the context-manager form.

Spans started with ``lane=True`` render on a per-trace synthetic track
instead of their thread's track: concurrent request roots from one submit
thread would otherwise overlap as siblings, which chrome-trace ``X``
nesting forbids (``ci/check_trace.py`` validates this invariant).
"""
from __future__ import annotations

import atexit
import collections
import json
import math
import os
import threading
import time

from jax.profiler import TraceAnnotation as _Annotation

from ..base import env_flag
from ..profiler import _now_us  # shared host timebase with mx.profiler
from .instrument import note_dispatch

__all__ = ["enabled", "session_live", "sample_rate", "trace_path",
           "buffer_cap", "SpanContext", "Span", "NULL_SPAN", "Tracer",
           "tracer", "start_trace", "span", "current", "record", "count",
           "snapshot", "export"]

_PID = 0                 # all host spans share one chrome-trace process
_LANE_BASE = 10_000_000  # synthetic per-trace track ids (lane=True spans)

_tls = threading.local()


# -- gates (read per call, like telemetry.instrument) -------------------------
session_live = _Annotation.is_enabled  # a jax.profiler trace is being taken


def enabled():
    """A profiler session is live, or ``MXNET_TRACE`` is set
    (base.env_flag falsy-string rule)."""
    return session_live() or env_flag("MXNET_TRACE")


def sample_rate():
    """``MXNET_TRACE_SAMPLE``: fraction of trace roots kept, clamped 0..1."""
    try:
        r = float(os.environ.get("MXNET_TRACE_SAMPLE", "1"))
    except ValueError:
        r = 1.0
    return min(max(r, 0.0), 1.0)


def trace_path():
    return os.environ.get("MXNET_TRACE_FILE", "mxtrace.json")


def buffer_cap():
    """``MXNET_TRACE_BUFFER``: ring capacity in finished spans."""
    try:
        n = int(os.environ.get("MXNET_TRACE_BUFFER", "16384"))
    except ValueError:
        n = 16384
    return max(n, 1)


def current():
    """Innermost span entered (``with span(...)``) on this thread, or None."""
    stack = getattr(_tls, "stack", None)
    return stack[-1] if stack else None


class SpanContext:
    """Cross-thread handle: ids plus the producer span's track and capture
    time.  Created by ``Span.context()``; consumed by ``span(name,
    parent=ctx)`` on any thread.  The flow ``"s"`` anchor is emitted lazily
    on the FIRST bind (not at capture): a context that is captured but never
    consumed — e.g. a traced request batched behind another trace's owner —
    must not leave an unmatched ``"s"`` in the export."""

    __slots__ = ("trace_id", "span_id", "tid", "ts_us", "emitted")

    def __init__(self, trace_id, span_id, tid, ts_us):
        self.trace_id = trace_id
        self.span_id = span_id
        self.tid = tid
        self.ts_us = ts_us
        self.emitted = False


class Span:
    """One started (possibly still open) span.  Use as a context manager
    for same-thread scoping (enters the thread-local stack so nested
    ``span()`` calls parent automatically), or call ``finish()`` explicitly
    for spans that end on another thread.  ``finish`` is idempotent: drop
    paths and dispatch paths may race to close a request span."""

    __slots__ = ("name", "trace_id", "span_id", "parent_id", "attrs",
                 "t0", "dur", "tid", "thread_name", "_tracer", "_ctx",
                 "_ann")

    def __init__(self, tracer, name, trace_id, parent_id=None, lane=False,
                 attrs=None):
        self._tracer = tracer
        self.name = name
        self.trace_id = trace_id
        self.span_id = tracer._new_id()
        self.parent_id = parent_id
        self.attrs = dict(attrs) if attrs else {}
        self.t0 = _now_us()
        self.dur = None
        if lane:
            self.tid = _LANE_BASE + trace_id
            self.thread_name = "trace-%d" % trace_id
        else:
            self.tid = threading.get_ident() % 1_000_000
            self.thread_name = threading.current_thread().name
        self._ctx = None
        self._ann = None  # (TraceAnnotation, attrs as it was opened with)

    def __bool__(self):
        return True

    def set(self, **attrs):
        self.attrs.update(attrs)
        return self

    def context(self):
        """Cross-thread handle, anchored at this span's track and the
        capture time (inside its eventual slice, so Perfetto binds the flow
        arrow to it).  The ``"s"`` event itself is emitted only when a
        consumer binds the context — see SpanContext."""
        if self._ctx is None:
            self._ctx = SpanContext(self.trace_id, self.span_id, self.tid,
                                    _now_us())
        return self._ctx

    def finish(self, **attrs):
        """Close the span and commit it to the ring (idempotent)."""
        if self.dur is not None:
            return self
        if attrs:
            self.attrs.update(attrs)
        self.dur = max(0.0, _now_us() - self.t0)
        self._tracer._record(self)
        return self

    def __enter__(self):
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        stack.append(self)
        if session_live():
            entered = dict(self.attrs)
            ann = _Annotation(self.name, trace=self.trace_id, **entered)
            ann.__enter__()
            self._ann = (ann, entered)
        return self

    def __exit__(self, *exc):
        if self._ann is not None:
            (ann, entered), self._ann = self._ann, None
            late = {k: v for k, v in self.attrs.items()
                    if k not in entered or entered[k] != v}
            if late:
                ann.set_metadata(**late)
            ann.__exit__(*exc)
        stack = getattr(_tls, "stack", None)
        if stack:
            if stack[-1] is self:
                stack.pop()
            elif self in stack:  # unbalanced exit: drop through to self
                del stack[stack.index(self):]
        self.finish()
        return False


class _NullSpan:
    """Shared no-op span: falsy, every method an identity/no-op.  The whole
    disabled/unsampled path allocates nothing."""

    __slots__ = ()

    def __bool__(self):
        return False

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self

    def finish(self, **attrs):
        return self

    def context(self):
        return None


NULL_SPAN = _NullSpan()


class Tracer:
    """Id allocation, systematic sampling, the bounded span ring, and the
    Chrome-trace exporter.  Policy-free like ``Registry``: constructing one
    never reads the env gate (tests do); gating lives in the module-level
    helpers."""

    def __init__(self, capacity=None):
        cap = capacity if capacity is not None else buffer_cap()
        self._mu = threading.Lock()
        self._spans = collections.deque(maxlen=cap)
        self._flows = collections.deque(maxlen=2 * cap)
        self._next = 1
        self._seen = 0

    # -- ids / sampling ------------------------------------------------------
    def _new_id(self):
        with self._mu:
            i = self._next
            self._next += 1
            return i

    def _sample(self):
        """Deterministic systematic sampling: over any window of N roots,
        exactly ``floor(N * rate)`` are kept (no RNG, reproducible tests)."""
        with self._mu:
            self._seen += 1
            n = self._seen
        r = sample_rate()
        return math.floor(n * r) > math.floor((n - 1) * r)

    def _record(self, span):
        self._spans.append(span)  # deque append is atomic under the GIL

    def _flow(self, ev):
        self._flows.append(ev)

    # -- span creation -------------------------------------------------------
    def start_trace(self, name, lane=False, **attrs):
        """Root span of a new trace, or NULL_SPAN when sampled out."""
        if not self._sample():
            return NULL_SPAN
        return Span(self, name, self._new_id(), None, lane=lane, attrs=attrs)

    def span(self, name, parent=None, lane=False, **attrs):
        """Child span of ``parent`` (Span | SpanContext | None ⇒ the
        thread-local current span).  No live parent ⇒ NULL_SPAN: only
        explicit roots start traces, so un-rooted hot paths (a bare kvstore
        push, a standalone Predictor call) record nothing."""
        if parent is None:
            parent = current()
        if not parent:
            return NULL_SPAN
        if isinstance(parent, SpanContext):
            sp = Span(self, name, parent.trace_id, parent.span_id, lane=lane,
                      attrs=attrs)
            # the "s" anchor (producer side) rides with the first "f" bind,
            # so s/f always enter the flow ring adjacent and paired
            with self._mu:
                emit_s = not parent.emitted
                parent.emitted = True
            if emit_s:
                self._flow({"name": "handoff", "cat": "flow", "ph": "s",
                            "id": parent.span_id,
                            "ts": round(parent.ts_us, 3), "pid": _PID,
                            "tid": parent.tid})
            # flow bind: arrow lands at this span's start on its thread
            self._flow({"name": "handoff", "cat": "flow", "ph": "f",
                        "bt": "e", "id": parent.span_id,
                        "ts": round(sp.t0, 3), "pid": _PID, "tid": sp.tid})
            return sp
        return Span(self, name, parent.trace_id, parent.span_id, lane=lane,
                    attrs=attrs)

    # -- export --------------------------------------------------------------
    def export_events(self):
        """→ chrome-trace event list: metadata (process/thread names +
        clock_sync), one "X" per finished span, then the flow events."""
        spans = list(self._spans)
        # flow events whose counterpart fell off the bounded ring (a long
        # run evicting oldest-first can cut through an s/f pair) would fail
        # ci/check_trace.py's matched-ids invariant — export only whole pairs
        by_id = {}
        for ev in self._flows:
            by_id.setdefault(ev["id"], set()).add(ev["ph"])
        flows = [ev for ev in self._flows if {"s", "f"} <= by_id[ev["id"]]]
        evs = [{"name": "process_name", "ph": "M", "pid": _PID,
                "args": {"name": "mxnet_tpu host spans"}},
               {"name": "clock_sync", "ph": "M", "pid": _PID,
                "args": {"unix_ts": round(time.time(), 6),
                         "trace_ts_us": round(_now_us(), 3)}}]
        tids = {}
        for s in spans:
            tids.setdefault(s.tid, s.thread_name)
        for tid, tname in sorted(tids.items()):
            evs.append({"name": "thread_name", "ph": "M", "pid": _PID,
                        "tid": tid, "args": {"name": tname}})
        for s in spans:
            args = {"trace": s.trace_id, "span": s.span_id}
            if s.parent_id is not None:
                args["parent"] = s.parent_id
            args.update(s.attrs)
            evs.append({"name": s.name, "cat": "span", "ph": "X",
                        "ts": round(s.t0, 3), "dur": round(s.dur, 3),
                        "pid": _PID, "tid": s.tid, "args": args})
        evs.extend(flows)
        return evs

    def clear(self):
        self._spans.clear()
        self._flows.clear()

    def export(self, path=None, reset=True):
        """Write Chrome-trace JSON → the path written (``trace_path()``
        default).  ``reset`` drains the ring so an atexit export after an
        explicit one never duplicates spans."""
        path = path if path is not None else trace_path()
        data = {"traceEvents": self.export_events(), "displayTimeUnit": "ms"}
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        with open(path, "w", encoding="utf-8") as f:
            json.dump(data, f, indent=1)
        if reset:
            self.clear()
        return path


# -- process-global tracer (mirrors instrument.registry) ----------------------
_mu = threading.Lock()
_tracer = None
_atexit_registered = False


def tracer():
    """The process-global Tracer (created lazily).  The atexit export to
    ``MXNET_TRACE_FILE`` is armed on the first access that sees tracing
    enabled — same late-enable contract as the telemetry JSONL sink."""
    global _tracer, _atexit_registered
    with _mu:
        if _tracer is None:
            _tracer = Tracer()
        if env_flag("MXNET_TRACE") and not _atexit_registered:
            atexit.register(_exit_export)
            _atexit_registered = True
        return _tracer


def _exit_export():
    with _mu:
        t = _tracer
    if t is not None and t._spans and env_flag("MXNET_TRACE"):
        try:
            t.export()
        except Exception:  # interpreter teardown: never mask the real exit
            pass


def _reset_for_tests():
    """Drop the global tracer (and any buffered spans)."""
    global _tracer
    with _mu:
        _tracer = None


# -- hot-path API -------------------------------------------------------------
def start_trace(name, lane=False, **attrs):
    """Begin a new trace → its root Span, or NULL_SPAN when tracing is off
    or this root is sampled out (a root under a live profiler session never
    is).  Off path: the session check and one env lookup."""
    if session_live():
        t = tracer()
        return Span(t, name, t._new_id(), None, lane=lane, attrs=attrs)
    if not env_flag("MXNET_TRACE"):
        return NULL_SPAN
    return tracer().start_trace(name, lane=lane, **attrs)


def span(name, parent=None, lane=False, **attrs):
    """Child span under ``parent`` (or the thread-local current span);
    NULL_SPAN when no trace is active here or tracing is off."""
    if parent is None and current() is None:
        return NULL_SPAN
    if not enabled():
        return NULL_SPAN
    return tracer().span(name, parent=parent, lane=lane, **attrs)


def record(name, start_s, end_s, **attrs):
    """Put a span that ended elsewhere into the ring: ``start_s`` /
    ``end_s`` are ``time.time()`` seconds (JAX stamps its compile stages so),
    moved onto ``_now_us``'s epoch, so they sit on one timeline with every
    other span here.  It joins the trace of the innermost span entered on
    this thread (a compile inside a traced step names its program there),
    else it is a root of its own, sampled as ``start_trace`` samples; on the
    thread's track, spans nest as their intervals do.  Tracing off:
    nothing, no tracer."""
    if not enabled():
        return
    t = tracer()
    parent = current()
    if parent is None and not session_live() and not t._sample():
        return
    shift_us = _now_us() - time.time() * 1e6
    sp = Span(t, name, parent.trace_id if parent else t._new_id(),
              parent.span_id if parent else None, attrs=attrs)
    sp.t0 = start_s * 1e6 + shift_us
    sp.dur = max(0.0, (end_s - start_s) * 1e6)
    t._record(sp)


def count(name, n=1, path=None):
    """Add ``n`` to the counter attr ``name`` of the innermost span on this
    thread; with no span entered, nothing.  ``count("dispatch")`` sits at
    every place that launches a program on the device, so the sum over one
    ``step`` root is that step's dispatches and the span that holds each
    says where it fell.  The train-step launch sites pass their ``path``
    ("fused", "fused_mesh", "legacy"): those also feed the registry's
    ``step_dispatches_total{path}`` when ``MXNET_TELEMETRY`` is on."""
    sp = current()
    if sp is not None:
        sp.attrs[name] = sp.attrs.get(name, 0) + n
    if path is not None:
        note_dispatch(n, path=path)


def snapshot():
    """The finished spans still in the ring, oldest first, as dicts:
    ``name``, ``trace`` (id shared by one root and all under it), ``span``,
    ``parent`` (span id or None), ``start_us`` (``mx.profiler``'s epoch),
    ``dur_us``, ``attrs`` (counters among them).  [] when nothing was ever
    traced."""
    with _mu:
        t = _tracer
    if t is None:
        return []
    return [{"name": s.name, "trace": s.trace_id, "span": s.span_id,
             "parent": s.parent_id, "start_us": s.t0, "dur_us": s.dur,
             "attrs": dict(s.attrs)} for s in list(t._spans)]


def export(path=None, reset=True):
    """Export buffered spans to Chrome-trace JSON; None when nothing was
    ever traced (no tracer exists)."""
    with _mu:
        t = _tracer
    if t is None:
        return None
    return t.export(path, reset=reset)
