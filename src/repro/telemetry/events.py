"""Typed span/event recorder: one per process, monotonic durations, spans
exportable on the wall clock.

The program's phases each become a SPAN named ``layer.what`` — the data
layer's ``data.wait``, ``data.place`` and ``data.h2d``, the step's
``step.dispatch``, and ``compile``, ``ckpt_write``, per-bucket
``collective``, serve ``prefill``/``decode`` — a dict
``{"kind", "ph": "span", "t0", "t1", "dur", "depth", **attrs}`` stamped
from ``time.monotonic()`` (durations must survive NTP jumps, which is also
why the cluster heartbeat rides these events — see the ``cluster.elastic``
staleness fix).  Instant events use ``"ph": "instant"``.

Clock.  Each recorder keeps one anchor, a monotonic reading and the
``time.time_ns()`` taken beside it, so :meth:`Recorder.wall_ns` puts any
of its timestamps on the wall clock: the clock of a JAX profiler trace's
``profile_start_time``.  :meth:`Recorder.spans_ns` exports the kept spans
as ``(name, start_ns, end_ns)`` on that clock, and the Chrome-trace export
(``telemetry.sinks``) uses it too, so a ``--trace-dir`` trace and a
profiler trace line up.  Each context-managed span also enters a
``jax.profiler.TraceAnnotation`` of its name, so a profile taken with the
host tracer on shows the program's spans over the device timeline.

One recorder per process.  :func:`process_recorder` is the recorder that
``Run``, ``Prefetcher``/``make_placer`` and ``Trainer`` use when they are
given none; ``make_recorder`` attaches a run's sinks to it and the run's
``close`` detaches them, while the aggregates (counters, gauges, the span
histograms ``hist("span/<kind>_s")``) outlive the run.  So code that
drives a run from outside — a benchmark — reads the program's spans from
the process recorder.

Bounded memory.  Its histograms keep their last 4096 observations; events go
to a ring of the most recent 4096 (a flight recorder) unless a sink writes
them.  Listeners (the cluster heartbeat writer, tests) see every event.

``NULL_RECORDER`` is the no-op recorder: code that is handed it pays one
attribute lookup and a constant context manager — no allocation, no
timestamp read.
"""
from __future__ import annotations

import os
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation

from repro.telemetry.metrics import (
    NULL_HISTOGRAM,
    WINDOW,
    Counter,
    Gauge,
    Histogram,
    metrics_summary,
)

# the cluster process index env var (repro.cluster.spec.ClusterSpec.env);
# read directly so telemetry stays importable without the cluster package
ENV_PROCESS_ID = "REPRO_PROCESS_ID"


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class NullRecorder:
    """The do-nothing recorder: every method is a constant-cost no-op, so
    library code can thread ``recorder.span(...)`` unconditionally."""
    __slots__ = ()
    enabled = False
    trace_dir = None
    process_index = 0

    def span(self, kind: str, **attrs):
        return _NULL_SPAN

    def event(self, kind: str, **attrs) -> None:
        pass

    def count(self, name: str, n: int = 1) -> None:
        pass

    def gauge(self, name: str, v: float) -> None:
        pass

    def hist(self, name: str):
        return NULL_HISTOGRAM

    def add_listener(self, fn: Callable) -> None:
        pass

    def close(self) -> None:
        pass


NULL_RECORDER = NullRecorder()


class _Span:
    """One open span; a context manager handed out by ``Recorder.span``."""
    __slots__ = ("rec", "kind", "attrs", "t0", "note")

    def __init__(self, rec: "Recorder", kind: str, attrs: dict):
        self.rec = rec
        self.kind = kind
        self.attrs = attrs
        self.t0 = 0.0
        self.note = TraceAnnotation(kind)

    def __enter__(self) -> "_Span":
        self.note.__enter__()
        self.rec._stack().append(self)
        self.t0 = self.rec.clock()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = self.rec.clock()
        self.note.__exit__(*exc)
        stack = self.rec._stack()
        # LIFO pop; tolerate out-of-order exits rather than corrupting depth
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:
            stack.remove(self)
        self.rec._finish(self.kind, self.t0, t1, len(stack), self.attrs)
        return False


class Recorder:
    """Collects completed events, notifies listeners and sinks, and
    aggregates metrics.  ``clock`` is injectable for deterministic tests.
    Spans may be recorded from several threads: each thread nests its own
    spans, and emission is serialized."""
    enabled = True
    trace_dir: Optional[str] = None

    def __init__(self, process: str = "main", process_index: int = 0,
                 clock: Callable[[], float] = time.monotonic):
        self.process = process
        self.process_index = process_index
        self.clock = clock
        # the anchor: one monotonic reading and the wall clock beside it
        self.anchor: Tuple[float, int] = (clock(), time.time_ns())
        self.events: deque = deque(maxlen=WINDOW)
        self._local = threading.local()
        self._lock = threading.Lock()
        self._listeners: List[Callable] = []
        self._sinks: List[Callable] = []
        self._counters: Dict[str, Counter] = {}
        self._gauges: Dict[str, Gauge] = {}
        self._hists: Dict[str, Histogram] = {}
        self._closed = False

    # -- clock -----------------------------------------------------------
    def wall_ns(self, t: float) -> int:
        """The wall-clock ns (``time.time_ns()``) of monotonic time ``t``."""
        mono, wall = self.anchor
        return wall + round((t - mono) * 1e9)

    def spans_ns(self) -> List[Tuple[str, int, int]]:
        """The ring's spans as ``(name, start_ns, end_ns)`` on the wall
        clock, the form a profiler trace's host spans take."""
        return [(e["kind"], self.wall_ns(e["t0"]), self.wall_ns(e["t1"]))
                for e in list(self.events) if e.get("ph") == "span"]

    # -- spans / events ------------------------------------------------
    def _stack(self) -> List[_Span]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def span(self, kind: str, **attrs) -> _Span:
        return _Span(self, kind, attrs)

    def record_span(self, kind: str, t0: float, t1: float, **attrs) -> None:
        """A span measured elsewhere, from ``t0`` to ``t1`` on
        :attr:`clock` (an observer thread timing work it waited on)."""
        self._finish(kind, t0, t1, 0, attrs)

    def _finish(self, kind: str, t0: float, t1: float, depth: int,
                attrs: dict) -> None:
        ev = {"kind": kind, "ph": "span", "t0": t0, "t1": t1,
              "dur": t1 - t0, "depth": depth, "tid": threading.get_ident()}
        ev.update(attrs)
        self.hist(f"span/{kind}_s").observe(ev["dur"])
        self._emit(ev)

    def event(self, kind: str, **attrs) -> None:
        ev = {"kind": kind, "ph": "instant", "t0": self.clock()}
        ev.update(attrs)
        self._emit(ev)

    def _emit(self, ev: dict) -> None:
        with self._lock:
            if self._sinks:
                for sink in self._sinks:
                    sink(ev)
            else:
                self.events.append(ev)
            listeners = list(self._listeners)
        for fn in listeners:
            fn(ev)

    # -- metrics -------------------------------------------------------
    def count(self, name: str, n: int = 1) -> None:
        c = self._counters.get(name)
        if c is None:
            c = self._counters.setdefault(name, Counter())
        c.add(n)

    def gauge(self, name: str, v: float) -> None:
        g = self._gauges.get(name)
        if g is None:
            g = self._gauges.setdefault(name, Gauge())
        g.set(v)

    def hist(self, name: str) -> Histogram:
        h = self._hists.get(name)
        if h is None:
            h = self._hists.setdefault(name, Histogram(WINDOW))
        return h

    def metrics(self) -> dict:
        return metrics_summary(dict(self._counters), dict(self._gauges),
                               dict(self._hists))

    # -- lifecycle -----------------------------------------------------
    def add_listener(self, fn: Callable) -> None:
        """``fn(ev)`` sees every completed event."""
        with self._lock:
            self._listeners.append(fn)

    def add_sink(self, sink: Callable) -> None:
        """``sink(ev)`` writes every completed event; while any sink is
        attached, the ring keeps nothing."""
        with self._lock:
            self._sinks.append(sink)

    def remove(self, fn: Callable) -> None:
        """Detach a listener or sink."""
        with self._lock:
            for group in (self._listeners, self._sinks):
                if fn in group:
                    group.remove(fn)

    def close(self) -> None:
        """Emit the final metrics snapshot and close closable listeners
        and sinks.  Idempotent — a second close is a no-op."""
        if self._closed:
            return
        self._closed = True
        self.event("metrics", **self.metrics())
        for fn in self._listeners + self._sinks:
            closer = getattr(fn, "close", None)
            if closer is not None:
                closer()


_PROCESS: Optional[Recorder] = None
_PROCESS_LOCK = threading.Lock()


def process_recorder() -> Recorder:
    """This process's recorder, made on first use.  Its process index
    comes from the cluster env (``REPRO_PROCESS_ID``) so per-process trace
    files never collide in multi-host runs."""
    global _PROCESS
    if _PROCESS is None:
        with _PROCESS_LOCK:
            if _PROCESS is None:
                idx = int(os.environ.get(ENV_PROCESS_ID, "0") or "0")
                _PROCESS = Recorder(process="main", process_index=idx)
    return _PROCESS


class RunRecorder:
    """One run's handle on the process recorder.  Spans, events and
    metrics go to the process recorder (so they outlive the run); the
    sinks and listeners the run attached are detached, and closed, by
    :meth:`close`."""
    enabled = True

    def __init__(self, rec: Recorder, trace_dir: Optional[str] = None):
        self.recorder = rec
        self.trace_dir = trace_dir
        self._attached: List[Callable] = []
        self._closed = False

    def __getattr__(self, name):
        # span, record_span, event, count, gauge, hist, metrics, clock,
        # wall_ns, spans_ns, anchor, process_index: the process recorder's
        return getattr(self.recorder, name)

    def add_listener(self, fn: Callable) -> None:
        self.recorder.add_listener(fn)
        self._attached.append(fn)

    def add_sink(self, sink: Callable) -> None:
        self.recorder.add_sink(sink)
        self._attached.append(sink)

    def close(self) -> None:
        """Emit the metrics snapshot, then detach and close this run's
        sinks and listeners.  Idempotent."""
        if self._closed:
            return
        self._closed = True
        self.recorder.event("metrics", **self.recorder.metrics())
        for fn in self._attached:
            self.recorder.remove(fn)
            closer = getattr(fn, "close", None)
            if closer is not None:
                closer()


def make_recorder(tspec=None, process: str = "train") -> RunRecorder:
    """One run's handle on the process recorder, from a ``TelemetrySpec``
    (or None).

    Always LIVE — event listeners (the cluster heartbeat) must work
    untraced — but without ``trace_dir`` nothing touches disk.  With it,
    a JSONL sink streams the run's events to ``trace_p<i>.jsonl``; its
    first line is a ``meta`` event carrying the recorder's clock anchor
    (``mono``, ``wall_ns``), from which the Chrome-trace export puts every
    timestamp on the wall clock."""
    rec = process_recorder()
    trace_dir = getattr(tspec, "trace_dir", None)
    handle = RunRecorder(rec, trace_dir)
    if trace_dir:
        from repro.telemetry.sinks import JsonlSink, trace_path
        os.makedirs(trace_dir, exist_ok=True)
        sink = JsonlSink(trace_path(trace_dir, rec.process_index))
        mono, wall = rec.anchor
        sink({"kind": "meta", "ph": "instant", "t0": rec.clock(),
              "process": process, "process_index": rec.process_index,
              "pid": os.getpid(), "clock": "monotonic", "mono": mono,
              "wall_ns": wall})
        handle.add_sink(sink)
    return handle
