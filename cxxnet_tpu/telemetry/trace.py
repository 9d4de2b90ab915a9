"""Lightweight span tracing over monotonic clocks, Chrome-trace export.

Dapper-style spans for the two pipelines this trainer runs — the
training step (data-wait -> host->device stage -> dispatch -> device
block -> eval -> checkpoint) and the serve request lifecycle
(queue-wait -> batch-assembly -> infer -> respond) — recorded into a
bounded ring buffer and exported as Chrome trace-event JSON
(``{"traceEvents": [...]}``), loadable in Perfetto / chrome://tracing.

Two levels of recording. ``enable()`` (the ``telemetry_trace=path``
knob) records every span of every category, feeds the distributed-trace
sink and is what ``dump`` exports. Without it the tracer records only
the categories named by ``keep()``: a run of the task driver keeps its
``cat="train"`` spans and its ``cat="setup"`` spans (``setup.*``,
``train.round``, the ``compile.*`` spans of telemetry/anomaly's compile
instrument) by default (``telemetry_steptime``; ``0`` keeps nothing),
for whoever reads a profiler dump afterwards
(telemetry/traceparse.attribute_profile, the benchmark's layer metrics).

Design constraints, in order:

* **not recorded is free**: an instrumentation point whose category is
  not recorded costs one attribute read and a set lookup (``span``
  returns a shared no-op context manager); production code can
  therefore bracket hot paths unconditionally. A span of a kept
  category costs one tuple, no lock and no syscall — about half a
  microsecond (PERF.md has the measurement); ``events()`` makes the
  dicts;
* **bounded**: the ring keeps the newest ``capacity`` events and counts
  what it dropped — a week-long run with tracing left on degrades to "the
  last N events", never to an OOM;
* **timeline-coherent**: all timestamps come from ``time.perf_counter()``
  (monotonic), so spans recorded from explicit begin/end pairs (e.g. the
  batcher's queue-wait, whose start is a request's submit time on another
  thread) land on the same timeline as context-manager spans.

Threading: events carry the recording thread's id, so nested spans on one
thread render as a flame stack and concurrent threads as parallel tracks
— exactly the Chrome trace-event "X" (complete-event) semantics.
"""

from __future__ import annotations

import copy
import json
import os
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional

from .registry import REGISTRY


class _NullSpan:
    """Shared no-op context manager — the disabled-tracer fast path."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()

#: shared no-op context manager, importable by hot paths that gate on
#: ``TRACER.enabled``/``DISTTRACE.enabled`` themselves (a fresh
#: ``contextlib.nullcontext()`` per step would be an allocation the
#: disabled-tracing contract forbids)
NULL_SPAN = _NULL_SPAN


class _Span:
    __slots__ = ("_tracer", "name", "cat", "args", "_t0")

    def __init__(self, tracer: "Tracer", name: str, cat: str,
                 args: Optional[Dict[str, Any]]):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self._tracer.add_complete(self.name, self._t0,
                                  time.perf_counter(),
                                  cat=self.cat, args=self.args)
        return False


class Tracer:
    """Bounded ring buffer of Chrome trace events; one process-global
    instance at :data:`TRACER`. ``enable()`` turns full recording on
    (the ``telemetry_trace=path`` knob does this via main.py);
    ``keep(cats)`` names the categories whose spans are recorded even
    without it (the train loop's, by default). A
    ``span``/``add_complete`` call of any other category, and every
    ``instant``, is a no-op until ``enable()``. The ring is the
    process's: it outlives any ``TelemetrySession``."""

    def __init__(self, capacity: int = 65536):
        self._lock = threading.Lock()
        self._buf: deque = deque(maxlen=capacity)
        self._enabled = False
        self._kept: frozenset = frozenset()
        self._t0 = time.perf_counter()
        self.dropped = 0
        self._thread_names: Dict[int, str] = {}
        # optional event sink (telemetry.disttrace): called with each
        # event BEFORE it reaches the ring — it may stamp distributed-
        # trace ids into args and/or consume the event into a
        # tail-exemplar buffer (return True = consumed). None when
        # distributed tracing is off, so the base tracer pays nothing.
        self._sink: Optional[Callable[[Dict[str, Any]], bool]] = None
        # extra keys merged into the dump's otherData — clock anchors,
        # wire clock-offset probes, process identity (disttrace owns
        # the content; the tracer only carries it into the export)
        self.extra_other: Dict[str, Any] = {}
        # ring-overflow drops as a registry counter: the dump's
        # otherData.dropped_events is only visible post-mortem, but a
        # week-long run's silent span loss must show on /metrics and in
        # tools/report.py while the run is still alive
        self._c_dropped = REGISTRY.counter(
            "cxxnet_trace_dropped_total",
            "Trace events dropped on span-ring overflow")

    # -- lifecycle -------------------------------------------------------
    @property
    def enabled(self) -> bool:
        return self._enabled

    def enable(self, capacity: Optional[int] = None) -> None:
        with self._lock:
            if capacity is not None and capacity != self._buf.maxlen:
                self._buf = deque(self._buf, maxlen=int(capacity))
            self._enabled = True

    def disable(self) -> None:
        self._enabled = False

    def keep(self, cats=()) -> None:
        """Record spans of these categories without ``enable()`` — into
        the same ring, skipping the sink, the thread-name table and the
        export. ``keep(())`` records nothing again."""
        self._kept = frozenset(cats)

    def clear(self) -> None:
        with self._lock:
            self._buf.clear()
            self._thread_names.clear()
            self.dropped = 0
            self._t0 = time.perf_counter()
            self.extra_other = {}

    def to_ts_us(self, perf_s: float) -> float:
        """Map a ``time.perf_counter()`` value onto this tracer's event
        timescale (microseconds since the ring's epoch) — the same
        coordinate every exported ``ts`` uses, so clock anchors recorded
        in it line up with the events they date."""
        return (perf_s - self._t0) * 1e6

    # -- recording -------------------------------------------------------
    def span(self, name: str, cat: str = "",
             args: Optional[Dict[str, Any]] = None):
        """``with tracer.span("serve.infer", args={...}):`` — records one
        complete ("X") event on exit. Free when not recorded."""
        if not self._enabled and cat not in self._kept:
            return _NULL_SPAN
        return _Span(self, name, cat, args)

    def add_complete(self, name: str, t0: float, t1: float,
                     cat: str = "", args: Optional[Dict[str, Any]] = None,
                     tid: Optional[int] = None) -> None:
        """Record a span from explicit ``time.perf_counter()`` begin/end
        values — for durations measured across threads (queue wait) or
        already measured before the tracer is consulted."""
        if not self._enabled:
            if cat in self._kept:
                # a kept category alone: one tuple into the ring (an
                # atomic append; the oldest falls out uncounted) and
                # nothing else — events() makes the dict
                self._buf.append((name, cat, t0, t1, tid if tid is not None
                                  else threading.get_ident(), args))
            return
        self._push(self._complete(name, cat, t0, t1,
                                  tid if tid is not None
                                  else threading.get_ident(), args))

    def _complete(self, name, cat, t0, t1, tid, args) -> Dict[str, Any]:
        ev = {
            "name": name,
            "ph": "X",
            "ts": (t0 - self._t0) * 1e6,            # microseconds
            "dur": max(t1 - t0, 0.0) * 1e6,
            "pid": os.getpid(),
            "tid": tid,
        }
        if cat:
            ev["cat"] = cat
        if args:
            ev["args"] = args
        return ev

    def instant(self, name: str, cat: str = "",
                args: Optional[Dict[str, Any]] = None) -> None:
        """A zero-duration marker (Chrome "i" event) — rollbacks,
        breaker trips, profile start/stop."""
        if not self._enabled:
            return
        ev = {
            "name": name,
            "ph": "i",
            "s": "t",                                # thread-scoped
            "ts": (time.perf_counter() - self._t0) * 1e6,
            "pid": os.getpid(),
            "tid": threading.get_ident(),
        }
        if cat:
            ev["cat"] = cat
        if args:
            ev["args"] = args
        self._push(ev)

    def _push(self, ev: Dict[str, Any]) -> None:
        sink = self._sink
        if sink is not None and sink(ev):
            return
        self._push_raw(ev)

    def set_sink(self, sink: Optional[Callable[[Dict[str, Any]], bool]]
                 ) -> None:
        """Install (or clear) the distributed-trace event sink — see
        ``_push``. One sink at a time; telemetry.disttrace owns it."""
        self._sink = sink

    def push_event(self, ev: Dict[str, Any]) -> None:
        """Append one pre-built Chrome event, bypassing the sink — the
        distributed layer uses this to flush events it already stamped
        (and possibly buffered), so they cannot re-enter the sink."""
        if not self._enabled:
            return
        self._push_raw(ev)

    def _push_raw(self, ev: Dict[str, Any]) -> None:
        t = threading.current_thread()
        overflow = False
        with self._lock:
            if t.ident is not None and t.ident not in self._thread_names:
                self._thread_names[t.ident] = t.name
            if len(self._buf) == self._buf.maxlen:
                self.dropped += 1
                overflow = True
            self._buf.append(ev)
        if overflow:
            self._c_dropped.inc()

    # -- reading / export ------------------------------------------------
    def events(self) -> List[Dict[str, Any]]:
        with self._lock:
            return self._events()

    def _events(self) -> List[Dict[str, Any]]:
        """The ring as dicts; the caller holds the lock (which a kept
        span's append does not take: copy again if one lands)."""
        while True:
            try:
                raw = list(self._buf)
                break
            except RuntimeError:
                continue
        return [self._complete(*e) if type(e) is tuple else e for e in raw]

    def dump(self, path: str) -> int:
        """Write the ring as Chrome trace-event JSON (perfetto-loadable);
        returns the event count. Thread-name metadata events are included
        so tracks carry readable names instead of bare tids."""
        with self._lock:
            events = self._events()
            names = dict(self._thread_names)
            dropped = self.dropped
            # deep copy: a shallow dict() would share the nested
            # clock_anchors list / clock_offsets dict, which background
            # threads closing root spans keep mutating while json.dumps
            # below runs outside the lock
            extra = copy.deepcopy(self.extra_other)
        meta = [{"name": "thread_name", "ph": "M", "pid": os.getpid(),
                 "tid": tid, "args": {"name": name}}
                for tid, name in sorted(names.items())]
        other = {"dropped_events": dropped,
                 "producer": "cxxnet_tpu.telemetry",
                 "pid": os.getpid()}
        other.update(extra)
        doc = {
            "traceEvents": meta + events,
            "displayTimeUnit": "ms",
            "otherData": other,
        }
        from ..io import stream
        payload = json.dumps(doc).encode("utf-8")
        if stream.is_remote(path):
            stream.write_bytes_atomic(path, payload)
        else:
            d = os.path.dirname(os.path.abspath(path))
            if d:
                os.makedirs(d, exist_ok=True)
            with open(path, "wb") as f:
                f.write(payload)
        return len(events)


# the process-global tracer every instrumentation point consults
TRACER = Tracer()


def get_tracer() -> Tracer:
    return TRACER
