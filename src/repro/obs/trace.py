"""Span tracer: the event source of the telemetry layer (DESIGN.md §13).

One process-global :class:`Tracer` collects timing *events* — nestable
spans, instant markers, and counter samples — into a thread-safe ring
buffer.  The fit path is instrumented against it with a fixed span
vocabulary (DESIGN.md §13.1): ``repro.fit`` (the estimator's fit),
``repro.step`` (one trainer step), ``repro.launch`` (one kernel
launch), ``repro.chunk`` (one fused ``StepProgram`` chunk),
``repro.read`` (the host blocking on device results), ``repro.view``
(one dataset view materialised), and K-Means' ``repro.init`` (a
restart's init draw) and ``repro.finish`` (its inertia and labels
passes).  The scheduler adds its admission,
gang-step chunk and elastic events (sched/scheduler.py), the allocator
its channel occupancy (sched/allocator.py).

Two sinks.  While ``enabled``, every event goes to the ring buffer,
with its args, on the tracer's own clock; the buffer renders to a
Chrome trace-event file via :mod:`repro.obs.chrome_trace` (``pim_jobs
--trace out.json`` or the ``REPRO_TRACE`` environment variable).  While
a JAX profiler session records (``jax.profiler.start_trace``), every
span also opens a ``jax.profiler.TraceAnnotation`` of the span's
constant name and no metadata, so the span lands in the profiler's
trace on the same clock as the device's events.  Instants and counters
go to the ring buffer only.

Overhead contract (asserted by tests/test_obs.py): both sinks are off
by default, and a span with both off costs one attribute check plus one
``TraceAnnotation.is_enabled()`` call before it returns the shared
no-op — no event dict, no timestamp, no lock.  Instants and counters
cost the attribute check alone.  Enabled, each ring-buffer event is one
``perf_counter`` pair and one deque append; the ring buffer (default
200k events) bounds memory on long-running services by dropping the
*oldest* events.

Tracks: every event names a ``track`` — a free-form string rendered as
its own timeline row.  The repo's taxonomy (DESIGN.md §13.2):

  ``sched``             scheduler control flow (admission, defragment)
  ``target:<name>``     per-execution-System timeline of chunk spans
  ``job:<name>``        per-job timeline (one row per tenant)
  ``fit``               estimator fits and their trainer steps
  ``system:<kind>``     launches, chunks, reads and views of one System
  ``channels:<name>``   per-memory-channel occupancy counters

Ring-buffer timestamps are microseconds of ``time.perf_counter()``
since tracer construction (monotonic; wall-clock anchoring travels in
the run metadata envelope, repro/obs/runmeta.py).  Spans measure
*host-visible* time: under jax async dispatch a launch span covers
dispatch plus any blocking the call itself performs.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from typing import Optional

from jax.profiler import TraceAnnotation

#: default ring-buffer capacity (events); ~100 B/event -> ~20 MB ceiling
DEFAULT_CAPACITY = 200_000


class _NullSpan:
    """Shared no-op context manager returned while tracing is disabled."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_SPAN = _NullSpan()


class _Span:
    """An open span; appends one complete ("X") event on exit, and
    mirrors itself into the profiler's trace while a session records."""

    __slots__ = ("_tracer", "_name", "_track", "_cat", "_args", "_t0",
                 "_annotation")

    def __init__(self, tracer: "Tracer", name: str, track: str, cat: str,
                 args: Optional[dict]):
        self._tracer = tracer
        self._name = name
        self._track = track
        self._cat = cat
        self._args = args
        self._annotation = (TraceAnnotation(name)
                            if TraceAnnotation.is_enabled() else None)

    def __enter__(self):
        if self._annotation is not None:
            self._annotation.__enter__()
        self._t0 = self._tracer.now_us()
        return self

    def __exit__(self, *exc):
        t = self._tracer
        t._append({"ph": "X", "name": self._name, "cat": self._cat,
                   "track": self._track, "ts": self._t0,
                   "dur": t.now_us() - self._t0,
                   "args": self._args or {}})
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
        return False


class Tracer:
    """Thread-safe ring buffer of trace events.

    ``enabled`` is the single hot-path gate: every emitting method
    checks it first and returns immediately when off.  Events are plain
    dicts (``ph``/``name``/``cat``/``track``/``ts``[/``dur``]/``args``)
    — the exporter maps ``track`` strings onto Chrome trace pid/tid
    pairs (repro/obs/chrome_trace.py)."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self.enabled = False
        self._events: deque = deque(maxlen=capacity)
        self._lock = threading.Lock()
        self._epoch = time.perf_counter()

    # -- control -------------------------------------------------------------

    def enable(self, capacity: Optional[int] = None) -> None:
        """Turn event collection on (idempotent).  ``capacity`` resizes
        the ring buffer, discarding buffered events."""
        if capacity is not None and capacity != self._events.maxlen:
            with self._lock:
                self._events = deque(self._events, maxlen=capacity)
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def clear(self) -> None:
        with self._lock:
            self._events.clear()

    def now_us(self) -> float:
        """Microseconds since tracer construction (monotonic)."""
        return (time.perf_counter() - self._epoch) * 1e6

    def _append(self, event: dict) -> None:
        # deque.append with maxlen is atomic under the GIL; the lock
        # only guards structural operations (events()/clear()/resize)
        self._events.append(event)

    # -- emission ------------------------------------------------------------

    def span(self, name: str, track: str = "main", cat: str = "default",
             **args):
        """Context manager timing a nested span on ``track``.

        Disabled with no profiler session recording: returns the shared
        no-op.  Disabled while one records: the profiler's
        ``TraceAnnotation(name)`` alone — ``name`` must then be a
        constant, and per-call detail goes into ``args``, which only
        the ring buffer keeps.  Spans on one track must nest (the
        exporter validates containment) — which they do by construction
        when emitted from ``with`` blocks on a single thread per
        track."""
        if self.enabled:
            return _Span(self, name, track, cat, args or None)
        if TraceAnnotation.is_enabled():
            return TraceAnnotation(name)
        return NULL_SPAN

    def instant(self, name: str, track: str = "main",
                cat: str = "default", **args) -> None:
        """A zero-duration marker (elastic preempt/resume/retry/...)."""
        if not self.enabled:
            return
        self._append({"ph": "i", "name": name, "cat": cat, "track": track,
                      "ts": self.now_us(), "args": args})

    def counter(self, name: str, value: float, track: str = "counters",
                cat: str = "counter") -> None:
        """Sample a numeric series (e.g. per-channel occupancy)."""
        if not self.enabled:
            return
        self._append({"ph": "C", "name": name, "cat": cat, "track": track,
                      "ts": self.now_us(), "args": {"value": value}})

    # -- inspection ----------------------------------------------------------

    def events(self) -> list:
        """Snapshot of the buffered events (oldest first)."""
        with self._lock:
            return list(self._events)

    def __len__(self) -> int:
        return len(self._events)


#: the process-global tracer every instrumentation site emits to
TRACER = Tracer()
