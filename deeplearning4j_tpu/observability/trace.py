"""Structured tracing: spans with explicit context handoff.

Counters say *how many* requests were shed or steps skipped; only a
trace says *why this one was slow* — was it queue wait, batch
assembly, an XLA recompile, a checkpoint restore mid-rollback? The
``Tracer``/``Span`` API here is deliberately tiny (a subset of the
OpenTelemetry shape) and built for this runtime's two awkward
realities:

- **threads, not coroutines**: a serving request crosses the handler
  thread, the admission path, and the MicroBatcher drain thread.
  There is no ambient context to ride on, so context handoff is
  EXPLICIT: the admitted work item carries its ``Span`` (or
  ``SpanContext``), and the drain thread starts children from it.
  One trace id follows the request end to end.
- **determinism is a test primitive**: ids come from a seeded RNG
  (``Tracer(seed=...)``), so a pinned seed replays the exact same
  trace/span ids — chaos runs and golden files can assert on them.

Finished spans land in a bounded in-memory ring (for tests and
``finished_spans()`` inspection) and, when a sink is attached, as
JSONL — one object per span/event — via ``JsonlSink`` (bounded by
rotation: at most ~2x ``max_bytes`` on disk, oldest half dropped).

A module-global tracer lets low-level primitives — the fit drivers,
checkpoint save/restore, retry attempts, breaker transitions, the
profiler — emit spans without threading a tracer through every
constructor. It records when ``set_global_tracer(Tracer(...))`` turned
it on, **or while a JAX profiler session runs**
(``jax.profiler.trace`` / ``start_trace`` / a remote capture);
otherwise every operation is a no-op costing one
``TraceAnnotation.is_enabled()`` call and a branch.

**One clock with the device.** While a profiler session runs, a
recording span also holds a ``jax.profiler.TraceAnnotation`` of its
name for its lifetime, so it is written into the session's
``.xplane.pb`` on the ``/host:CPU`` plane (one line per Python
thread), on the time axis of ``/device:TPU:<n>``'s ``XLA Ops``, with
its scalar attrs as the event's stats. That file is the shared clock:
an idle gap on the device can be laid against what the host was doing
in it. In memory spans are on ``time.perf_counter``. An interval known
only once it has ended (jax's compile phases, ``compile/persistent.py``)
enters as a finished span through ``Tracer.record``, and not into that
file.
"""

from __future__ import annotations

import json
import os
import random
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Union

_trace_annotation = None  # jax.profiler.TraceAnnotation, on first use


def _annotation_cls():
    global _trace_annotation
    if _trace_annotation is None:
        from jax.profiler import TraceAnnotation

        _trace_annotation = TraceAnnotation
    return _trace_annotation


def profiler_session_active() -> bool:
    """True exactly while a JAX profiler session is capturing: the
    switch the device trace already has."""
    return _annotation_cls().is_enabled()


class SpanContext:
    """The portable identity of a span: what you hand to another
    thread so its spans join your trace."""

    __slots__ = ("trace_id", "span_id")

    def __init__(self, trace_id: str, span_id: str):
        self.trace_id = trace_id
        self.span_id = span_id

    def __repr__(self) -> str:
        return f"SpanContext({self.trace_id}, {self.span_id})"


class Span:
    """One named, timed operation. End it exactly once (``end()`` or
    the context-manager form, which also marks error status on an
    exception). Attribute/event mutation is single-writer by
    convention (the thread that owns the span)."""

    __slots__ = ("tracer", "name", "trace_id", "span_id", "parent_id",
                 "start_time", "end_time", "attrs", "events", "status",
                 "_ended", "_annotation")
    recording = True  # False on NOOP_SPAN: guards costly attrs

    def __init__(self, tracer: "Tracer", name: str, trace_id: str,
                 span_id: str, parent_id: Optional[str],
                 start_time: float, attrs: Optional[dict] = None,
                 annotate: bool = True):
        self.tracer = tracer
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.start_time = start_time
        self.end_time: Optional[float] = None
        self.attrs: Dict[str, object] = dict(attrs) if attrs else {}
        self.events: List[dict] = []
        self.status = "ok"
        self._ended = False
        self._annotation = None
        if annotate and profiler_session_active():
            # the same interval in the profiler's own file, beside the
            # device's timeline
            self._annotation = _annotation_cls()(name)
            self._annotation.__enter__()

    @property
    def context(self) -> SpanContext:
        return SpanContext(self.trace_id, self.span_id)

    def set_attr(self, key: str, value) -> "Span":
        self.attrs[key] = value
        return self

    def add_event(self, name: str, **attrs) -> "Span":
        self.events.append({
            "name": name, "time": self.tracer.clock(), "attrs": attrs,
        })
        return self

    def end(self, status: Optional[str] = None) -> None:
        if self._ended:  # idempotent: double-end keeps the first record
            return
        self._ended = True
        if status is not None:
            self.status = status
        self.end_time = self.tracer.clock()
        ann = self._annotation
        if ann is not None:
            self._annotation = None
            meta = {k: v for k, v in self.attrs.items()
                    if isinstance(v, (bool, int, float, str))}
            if self.status != "ok":
                meta["status"] = self.status
            if meta:
                ann.set_metadata(**meta)
            ann.__exit__(None, None, None)
        self.tracer._finish(self)

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc_type is not None and self.status == "ok":
            self.status = "error"
            self.attrs.setdefault("error_type", exc_type.__name__)
        self.end()

    def to_dict(self) -> dict:
        return {
            "kind": "span",
            "name": self.name,
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "start": self.start_time,
            "end": self.end_time,
            "duration_ms": (
                (self.end_time - self.start_time) * 1000.0
                if self.end_time is not None else None
            ),
            "status": self.status,
            "attrs": self.attrs,
            "events": self.events,
        }


class _NoopSpan:
    """Shared do-nothing span for disabled tracers: the hot path pays
    one flag check + one attribute lookup, nothing else."""

    __slots__ = ()
    recording = False
    context = SpanContext("", "")
    trace_id = ""
    span_id = ""

    def set_attr(self, key, value):
        return self

    def add_event(self, name, **attrs):
        return self

    def end(self, status=None):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *a):
        pass


NOOP_SPAN = _NoopSpan()


class JsonlSink:
    """Bounded JSONL span/event sink: one JSON object per line,
    flushed per write (a crash loses at most the in-flight line).
    When the live file exceeds ``max_bytes`` it rotates to
    ``<path>.1`` (replacing the previous rotation), so disk usage is
    bounded at ~2x ``max_bytes`` however long the process runs."""

    def __init__(self, path, max_bytes: int = 8 << 20):
        if max_bytes < 1:
            raise ValueError("max_bytes must be >= 1")
        self.path = os.fspath(path)
        self.max_bytes = max_bytes
        self._lock = threading.Lock()
        self._f = open(self.path, "a", encoding="utf-8")
        self._size = self._f.tell()
        self.written = 0
        self.rotations = 0

    def write(self, record: dict) -> None:
        line = json.dumps(record, default=str) + "\n"
        data = line.encode("utf-8")
        with self._lock:
            if self._size + len(data) > self.max_bytes and self._size:
                self._f.close()
                os.replace(self.path, self.path + ".1")
                self._f = open(self.path, "a", encoding="utf-8")
                self._size = 0
                self.rotations += 1
            self._f.write(line)
            self._f.flush()
            self._size += len(data)
            self.written += 1

    def close(self) -> None:
        with self._lock:
            self._f.close()


class Tracer:
    """Span factory + finished-span collector (see module docstring).

    ``seed`` pins the id sequence (deterministic traces under test);
    ``clock`` is injectable; ``sink`` receives every finished span as
    a dict (``JsonlSink`` or anything with ``write(dict)``);
    ``enabled=False`` makes every operation a no-op, unless
    ``follow_profiler`` is set (the default global tracer's mode):
    such a tracer also records while a JAX profiler session runs."""

    def __init__(self, seed: Optional[int] = None, sink=None,
                 clock: Callable[[], float] = time.perf_counter,
                 max_finished: int = 2048, enabled: bool = True,
                 follow_profiler: bool = False):
        self.enabled = enabled
        self.follow_profiler = follow_profiler
        self.clock = clock
        self.sink = sink
        self._rng = random.Random(seed)
        self._lock = threading.Lock()
        self._finished: "deque[Span]" = deque(maxlen=max_finished)

    def _new_ids(self) -> "tuple[str, str]":
        with self._lock:
            return (f"{self._rng.getrandbits(128):032x}",
                    f"{self._rng.getrandbits(64):016x}")

    def _child_id(self) -> str:
        with self._lock:
            return f"{self._rng.getrandbits(64):016x}"

    def is_recording(self) -> bool:
        """Whether a span started now would be kept."""
        return self.enabled or (
            self.follow_profiler and profiler_session_active())

    def _ids_under(self, parent) -> "tuple[str, str, Optional[str]]":
        """(trace id, span id, parent id) of a span started under
        ``parent``; a root of a new trace without one."""
        if isinstance(parent, _NoopSpan):
            parent = None
        if parent is not None and parent.trace_id:
            return parent.trace_id, self._child_id(), parent.span_id
        return (*self._new_ids(), None)

    def start_span(self, name: str,
                   parent: Union[Span, SpanContext, None] = None,
                   attrs: Optional[dict] = None) -> Union[Span, _NoopSpan]:
        if not self.is_recording():
            return NOOP_SPAN
        return Span(self, name, *self._ids_under(parent),
                    self.clock(), attrs)

    def record(self, name: str, start: float, end: float,
               attrs: Optional[dict] = None,
               parent: Union[Span, SpanContext, None] = None,
               ) -> Optional[Span]:
        """Keep a finished span for an interval that has already ended
        (``start``/``end`` on this tracer's clock): what a listener
        learns after the fact, such as jax's compile phases. Nothing
        when the tracer is not recording. It never enters a profiler
        session's file: the interval is over before it is known."""
        if not self.is_recording():
            return None
        span = Span(self, name, *self._ids_under(parent), start, attrs,
                    annotate=False)
        span.end_time = end
        span._ended = True
        self._finish(span)
        return span

    def event(self, name: str, attrs: Optional[dict] = None,
              parent: Union[Span, SpanContext, None] = None) -> None:
        """A zero-duration record (breaker tripped, compile observed,
        retry attempt N failed) — a span whose start == end."""
        self.start_span(name, parent=parent, attrs=attrs).end()

    def _finish(self, span: Span) -> None:
        with self._lock:
            self._finished.append(span)
        if self.sink is not None:
            try:
                self.sink.write(span.to_dict())
            except Exception:
                pass  # telemetry must never take down the work

    def finished_spans(self) -> List[Span]:
        with self._lock:
            return list(self._finished)

    def clear(self) -> None:
        with self._lock:
            self._finished.clear()


# -- global tracer ------------------------------------------------------

_global_tracer = Tracer(enabled=False, follow_profiler=True)
_global_lock = threading.Lock()


def get_tracer() -> Tracer:
    """The process-global tracer low-level primitives (the fit
    drivers, checkpoint, retry, breaker, profiler) emit through. Until
    ``set_global_tracer`` installs another it records only while a JAX
    profiler session runs."""
    return _global_tracer


def set_global_tracer(tracer: Tracer) -> Tracer:
    """Install ``tracer`` globally; returns the previous one so tests
    can restore it."""
    global _global_tracer
    with _global_lock:
        prev = _global_tracer
        _global_tracer = tracer
        return prev
