"""Span tracing inside the program: one recorder, one clock.

The latency percentiles (serve) and step windows (train) say HOW LONG;
spans say WHERE the milliseconds went: which phase of the batcher
thread's cycle, of the engine's call, of the input pipeline.  A span is
a dict —

    {trace_id, span_id, parent_id, name, start_s, duration_s, thread,
     ...attrs}

— appended to the tracer's bounded in-memory ring (``snapshot()`` reads
it; the benchmark's per-layer readers do) and, when the tracer was given
a ``Telemetry``, also emitted as one ``trace.span`` event, so spans
inherit the bus's sinks, per-host files and crash semantics, and
``tools/trace_export.py`` converts them to Chrome/Perfetto JSON offline.

One clock: every start and end is ``time.perf_counter()``, the clock the
benchmark anchors onto the device trace (an operator's profile holds the
device alone, ``obs/trace.py::operator_profile_options``;
``tools/trace_export.py --profile`` places the spans beside it by one
anchor).  Parents may be recorded after their children (a root span's
duration isn't known until it ends); consumers must not assume order.

Arming: nothing installs a tracer by default.  A site asks
``active(telemetry)`` — ``telemetry.spans`` when a CLI consumer armed
one, else the tracer installed for the process (``install``), else None —
and takes its unchanged code path on None.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import os
import threading
import time
from typing import Optional

RING_SPANS = 65536  # a 30 s serve window records about 4,500

_installed: Optional["SpanTracer"] = None


def install(tracer: "SpanTracer") -> "SpanTracer":
    """Make ``tracer`` the process's tracer: sites with no
    ``telemetry.spans`` of their own record into it."""
    global _installed
    _installed = tracer
    return tracer


def uninstall() -> None:
    global _installed
    _installed = None


def active(telemetry=None) -> Optional["SpanTracer"]:
    """The tracer a site should record into, or None (tracing off)."""
    tr = getattr(telemetry, "spans", None) if telemetry is not None else None
    return tr if tr is not None else _installed


def self_time(span: dict, children) -> float:
    """``span``'s duration less the part of it that ``children`` (the
    spans whose ``parent_id`` is its id) cover."""
    lo = span["start_s"]
    hi = lo + span["duration_s"]
    covered, edge = 0.0, lo
    for s, e in sorted((c["start_s"], c["start_s"] + c["duration_s"])
                       for c in children):
        s, e = max(s, edge), min(e, hi)
        if e > s:
            covered += e - s
            edge = e
    return span["duration_s"] - covered


class Span:
    """An open span: a context manager that stamps both ends.  ``attrs``
    may be filled while it is open (a count known only at the end)."""

    __slots__ = ("_tracer", "_outer", "name", "trace_id", "span_id",
                 "parent_id", "start", "thread", "attrs")

    def __init__(self, tracer, name, trace_id, parent_id, attrs):
        self._tracer = tracer
        self.name = name
        self.trace_id = trace_id
        self.parent_id = parent_id
        self.span_id = tracer.new_span_id()
        self.attrs = attrs

    def _adopt(self, outer: Optional["Span"]) -> None:
        """Parent and trace default to ``outer``'s, the span open on the
        opening thread (a new trace when there is none)."""
        if outer is not None:
            if self.parent_id is None:
                self.parent_id = outer.span_id
            if self.trace_id is None:
                self.trace_id = outer.trace_id
        if self.trace_id is None:
            self.trace_id = self._tracer.new_trace_id(self.name)
        self.thread = threading.current_thread().name

    def __enter__(self) -> "Span":
        tr = self._tracer
        self._outer = getattr(tr._local, "span", None)
        self._adopt(self._outer)
        tr._local.span = self
        self.start = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        end = time.perf_counter()
        self._tracer._local.span = self._outer
        if exc_type is not None:
            self.attrs["error"] = exc_type.__name__
        self._record(end)
        return False

    def begin(self) -> "Span":
        """Stamp the start of a span that may END ON ANOTHER THREAD
        (``finish()`` there): a launch that the batcher thread assembles
        and a lane runs.  It does not become this thread's open span;
        whoever works on it makes it the open one with ``span.under()``.
        It is recorded on the lane of the thread that began it."""
        self._adopt(self._tracer.current())
        self.start = time.perf_counter()
        return self

    @contextlib.contextmanager
    def under(self):
        """Make this begun span the calling thread's open one for the
        block: spans opened inside are its children, on whichever thread."""
        local, outer = self._tracer._local, self._tracer.current()
        local.span = self
        try:
            yield self
        finally:
            local.span = outer

    def finish(self) -> None:
        self._record(time.perf_counter())

    def _record(self, end: float) -> None:
        self._tracer.emit(trace_id=self.trace_id, name=self.name,
                          start=self.start, end=end, span_id=self.span_id,
                          parent_id=self.parent_id, thread=self.thread,
                          **self.attrs)


class SpanTracer:
    """Mints ids and records spans: into its ring always, onto the bus
    when it has one.

    Ids carry the pid plus a short random tag so traces from several
    hosts/processes joined into one artifact can't collide — pid alone
    is not enough: two containerised replicas typically BOTH run as
    pid 1.  The per-process counter keeps ids cheap within a run.
    Thread-safe: ``itertools.count`` and ``deque.append`` are atomic
    under CPython, the open-span stack is per thread, and emission goes
    through the bus's own lock.
    """

    def __init__(self, telemetry=None, *, prefix: Optional[str] = None,
                 capacity: int = RING_SPANS):
        self._tel = telemetry
        self.prefix = (prefix if prefix is not None
                       else f"{os.getpid():x}{os.urandom(2).hex()}")
        self._ids = itertools.count(1)
        self._ring: collections.deque = collections.deque(maxlen=capacity)
        self._local = threading.local()

    def new_trace_id(self, hint: str = "") -> str:
        tag = f"{hint}-" if hint else ""
        return f"{tag}{self.prefix}-{next(self._ids):x}"

    def new_span_id(self) -> str:
        return f"s{self.prefix}-{next(self._ids):x}"

    def span(self, name: str, *, trace_id: Optional[str] = None,
             parent_id: Optional[str] = None, **attrs) -> Span:
        """Open a span: ``with tracer.span("serve.pad", bytes=n) as sp``.
        Parent and trace default to the span open on this thread (a new
        trace when there is none); pass ``trace_id`` to root a new trace
        under a parent of another (a batch under the thread's cycle)."""
        return Span(self, name, trace_id, parent_id, attrs)

    def current(self) -> Optional[Span]:
        """The innermost span open on the calling thread."""
        return getattr(self._local, "span", None)

    def emit(self, *, trace_id: str, name: str, start: float, end: float,
             span_id: Optional[str] = None, parent_id: Optional[str] = None,
             step: Optional[int] = None, **attrs) -> str:
        """Record one completed span whose ends were stamped elsewhere
        (``perf_counter``); returns its span_id (pre-mint with
        ``new_span_id()`` to record children before their parent)."""
        sid = span_id if span_id is not None else self.new_span_id()
        record = {"trace_id": trace_id, "span_id": sid,
                  "parent_id": parent_id, "name": name,
                  "start_s": round(float(start), 6),
                  "duration_s": round(max(float(end) - float(start), 0.0),
                                      6),
                  **attrs}
        self._ring.append(record)
        if self._tel is not None:
            self._tel.emit("trace.span", step=step, **record)
        return sid

    def snapshot(self) -> list:
        """The ring's spans, oldest first."""
        return list(self._ring)
