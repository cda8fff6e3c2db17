"""Flight recorder and spans: bounded rings of run events and timed spans.

The run-health counterpart of :mod:`repro.obs.metrics`: where the registry
aggregates (counters/gauges/timers), the recorder keeps the *sequence* —
what happened, in what order, right up to the moment a long forecast blew
up. Design constraints mirror the metrics switchboard:

  * **Zero overhead when disabled.** No recorder installed means the
    module hooks :func:`record` and :func:`crash_dump` are one attribute
    check each.
  * **Bounded memory.** The ring holds the last ``capacity`` events
    (``deque(maxlen=...)``); older events are dropped (and counted in
    ``dropped``) — a million-step forecast can record every probe without
    growing without bound.
  * **Crash-survivable.** With a sink configured (``REPRO_EVENT_LOG=path``
    or ``FlightRecorder(sink=...)``) every event is appended to the JSONL
    file *as it is recorded* (line-buffered + flushed), so a hard crash
    still leaves the log on disk. The first line of the sink is a ``meta``
    event carrying :func:`repro.obs.report.runtime_metadata`. On a managed
    abort, :meth:`FlightRecorder.crash_dump` additionally writes the whole
    ring (plus metadata and the abort reason) as one JSON document.

Event timestamps are ``time.monotonic()`` (ordering/durations are immune
to wall-clock steps) plus ``time.time()`` for cross-run correlation.

Spans (:func:`span`, :func:`record_span`) are the one timing primitive of
the instrumented layers, and they are always on. Each finished span is a
:class:`Span` (name, ``time.perf_counter_ns()`` start and end, its own id,
the id of the span open around it, and an optional request id and batch
number) in a module-level ring of the last :data:`SPAN_CAPACITY` spans
(:func:`spans`, :func:`spans_dropped`). A ``with`` span also opens a
``jax.profiler.TraceAnnotation`` of its name, so a captured profiler trace
shows it on the host plane, on the device ops' clock. With a metrics
registry installed a span observes its duration under its name; with a
recorder installed it records one event of its name with ``duration_s``.
Without a capture, a registry or a recorder a ``with`` span costs two
clock reads, an idle annotation and a ring append (about 1.8 us on a TPU
v5e host), an after-the-fact span about 0.7 us.
Like the registry, the span stack is not thread-safe: one Python thread
drives the instrumented paths.
"""

from __future__ import annotations

import dataclasses
import itertools
import json
import os
import time
from collections import deque
from contextlib import contextmanager
from pathlib import Path
from typing import Any

from repro.obs import metrics

EVENT_LOG_ENV = "REPRO_EVENT_LOG"
DEFAULT_CAPACITY = 4096


@dataclasses.dataclass(frozen=True)
class Event:
    """One structured run event."""

    seq: int              # recorder-local sequence number (total order)
    ts: float             # time.monotonic() at record time
    wall: float           # time.time() at record time
    kind: str             # dotted event name, e.g. "health.blowup"
    data: dict[str, Any]  # free-form JSON-serialisable payload

    def as_dict(self) -> dict[str, Any]:
        return {
            "seq": self.seq,
            "ts": self.ts,
            "wall": self.wall,
            "kind": self.kind,
            "data": self.data,
        }


class FlightRecorder:
    """Bounded ring buffer of :class:`Event` with an optional JSONL sink."""

    def __init__(
        self,
        capacity: int = DEFAULT_CAPACITY,
        sink: str | Path | None = None,
    ) -> None:
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self.sink = Path(sink) if sink else None
        self.dropped = 0
        self._ring: deque[Event] = deque(maxlen=capacity)
        self._seq = 0
        self._file = None  # lazily opened append handle
        self._header_written = False  # once per recorder, even across close/reopen

    # -- recording ---------------------------------------------------------
    def record(self, kind: str, **data: Any) -> Event:
        ev = Event(seq=self._seq, ts=time.monotonic(), wall=time.time(),
                   kind=kind, data=data)
        self._seq += 1
        if len(self._ring) == self.capacity:
            self.dropped += 1
        self._ring.append(ev)
        if self.sink is not None:
            self._write_line(ev)
        return ev

    # -- inspection --------------------------------------------------------
    def events(self, kind: str | None = None) -> list[Event]:
        """A snapshot of the ring, optionally filtered by exact kind."""
        if kind is None:
            return list(self._ring)
        return [e for e in self._ring if e.kind == kind]

    def __len__(self) -> int:
        return len(self._ring)

    # -- sink / dump -------------------------------------------------------
    def _metadata(self) -> dict[str, Any]:
        """Best-effort runtime stamp: recorder I/O must never take the run
        down (and must not force a jax backend if one can't initialise)."""
        try:
            from repro.obs.report import runtime_metadata

            return runtime_metadata()
        except Exception as e:  # pragma: no cover - backend-dependent
            return {"error": f"runtime_metadata unavailable: {e!r}"}

    def _write_line(self, ev: Event) -> None:
        if self._file is None:
            self.sink.parent.mkdir(parents=True, exist_ok=True)
            self._file = self.sink.open("a", buffering=1)
            if not self._header_written:
                header = {"seq": -1, "ts": time.monotonic(), "wall": time.time(),
                          "kind": "meta", "data": self._metadata()}
                self._file.write(json.dumps(header, default=str) + "\n")
                self._header_written = True
        self._file.write(json.dumps(ev.as_dict(), default=str) + "\n")
        self._file.flush()

    def crash_dump(self, path: str | Path | None = None, *,
                   reason: str = "") -> Path | None:
        """Flushes the whole ring (+ metadata + ``reason``) as one JSON
        document — the abort-path artifact. Default target: the sink path
        with ``.crash.json`` appended; returns None (no-op) when neither a
        path nor a sink is configured (the in-memory ring remains
        inspectable via :meth:`events`)."""
        if path is None:
            if self.sink is None:
                return None
            path = self.sink.with_name(self.sink.name + ".crash.json")
        path = Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        payload = {
            "reason": reason,
            "metadata": self._metadata(),
            "dropped": self.dropped,
            "events": [e.as_dict() for e in self._ring],
        }
        path.write_text(json.dumps(payload, indent=2, default=str) + "\n")
        return path

    def close(self) -> None:
        """Closes the sink file handle. Safe to keep using the recorder:
        the next sink write lazily reopens in append mode (without
        duplicating the ``meta`` header)."""
        if self._file is not None:
            self._file.close()
            self._file = None


# --- module-level switchboard (mirrors repro.obs.metrics) ------------------

_RECORDER: FlightRecorder | None = None


def current() -> FlightRecorder | None:
    """The active recorder, or None when event logging is disabled."""
    return _RECORDER


def enabled() -> bool:
    return _RECORDER is not None


def enable(recorder: FlightRecorder | None = None) -> FlightRecorder:
    """Installs ``recorder`` (or a fresh sink-less one) as active. A
    different recorder being replaced has its sink handle closed — the
    switchboard owns the fd of whatever it installed (re-enabling the old
    recorder later is safe: the sink lazily reopens)."""
    global _RECORDER
    rec = recorder if recorder is not None else FlightRecorder()
    if _RECORDER is not None and _RECORDER is not rec:
        _RECORDER.close()
    _RECORDER = rec
    return _RECORDER


def disable() -> None:
    """Uninstalls (and closes the sink handle of) the active recorder."""
    global _RECORDER
    if _RECORDER is not None:
        _RECORDER.close()
    _RECORDER = None


@contextmanager
def using(recorder: FlightRecorder | None = None):
    """Scoped :func:`enable`: restores the previous recorder on exit and
    closes the scoped one's sink handle (its ring stays inspectable)."""
    global _RECORDER
    prev = _RECORDER
    rec = recorder if recorder is not None else FlightRecorder()
    _RECORDER = rec
    try:
        yield rec
    finally:
        _RECORDER = prev
        if rec is not prev:
            rec.close()


# -- zero-overhead convenience hooks (instrumented layers call these) -------


def record(kind: str, **data: Any) -> Event | None:
    if _RECORDER is not None:
        return _RECORDER.record(kind, **data)
    return None


def crash_dump(path: str | Path | None = None, *, reason: str = "") -> Path | None:
    if _RECORDER is not None:
        return _RECORDER.crash_dump(path, reason=reason)
    return None


# -- spans ------------------------------------------------------------------

SPAN_CAPACITY = 16384


class Span:
    """One span; times are ``time.perf_counter_ns()``. :func:`span` returns
    it open, and ``rid``, ``batch`` and ``data`` (the recorder event's
    payload) may be set inside the ``with`` block, for what is known only
    once the work has begun. The ring holds it once it has ended."""

    __slots__ = ("name", "start_ns", "end_ns", "id", "parent", "rid", "batch",
                 "data", "_annotation")

    def __init__(self, name: str, rid: int | None = None, batch: int | None = None,
                 data: dict[str, Any] | None = None) -> None:
        self.name = name
        self.rid = rid          # request id
        self.batch = batch      # batch number
        self.data = data
        self.parent = None      # id of the span open around this one

    @property
    def duration_s(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9

    def __repr__(self) -> str:
        return (f"Span({self.name!r}, id={self.id}, parent={self.parent}, "
                f"rid={self.rid}, batch={self.batch}, "
                f"duration_s={self.duration_s:.3g})")

    def __enter__(self) -> "Span":
        self._annotation = _annotation(self.name)
        self._annotation.__enter__()
        if _OPEN:
            self.parent = _OPEN[-1]
        self.id = sid = next(_SPAN_IDS)
        _OPEN.append(sid)
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self.end_ns = time.perf_counter_ns()
        _OPEN.pop()
        self._annotation.__exit__(*exc)
        self._annotation = None
        _finish(self)
        return False


_SPANS: deque[Span] = deque(maxlen=SPAN_CAPACITY)
_SPAN_IDS = itertools.count()
_OPEN: list[int] = []   # ids of the open ``with`` spans, innermost last
_spans_dropped = 0
_annotation_cls = None


def _annotation(name: str):
    global _annotation_cls
    if _annotation_cls is None:
        import jax.profiler

        _annotation_cls = jax.profiler.TraceAnnotation
    return _annotation_cls(name)


def _finish(sp: Span) -> None:
    global _spans_dropped
    if len(_SPANS) == _SPANS.maxlen:
        _spans_dropped += 1
    _SPANS.append(sp)
    if metrics.current() is not None:
        metrics.observe(sp.name, sp.duration_s)
    if _RECORDER is not None:
        tags = {k: v for k, v in (("rid", sp.rid), ("batch", sp.batch)) if v is not None}
        _RECORDER.record(sp.name, duration_s=sp.duration_s, **tags, **(sp.data or {}))


def span(name: str, *, rid: int | None = None, batch: int | None = None,
         **data: Any) -> Span:
    """Times a ``with`` block as a span named ``name``, a child of the
    innermost open span, under a profiler annotation of the same name.
    ``data`` goes to the recorder's event only."""
    return Span(name, rid, batch, data)


def record_span(name: str, start_ns: int, end_ns: int, *, rid: int | None = None,
                batch: int | None = None, **data: Any) -> Span:
    """Records a span after the fact from two ``time.perf_counter_ns()``
    stamps, for an interval no ``with`` block brackets (a request's wait in
    the queue). It has no parent and no profiler annotation."""
    sp = Span(name, rid, batch, data)
    sp.start_ns, sp.end_ns, sp.id = start_ns, end_ns, next(_SPAN_IDS)
    _finish(sp)
    return sp


def spans() -> list[Span]:
    """A snapshot of the span ring, oldest first."""
    return list(_SPANS)


def spans_dropped() -> int:
    """How many spans have fallen off the ring since the process started."""
    return _spans_dropped


if os.environ.get(EVENT_LOG_ENV, "").strip():
    enable(FlightRecorder(sink=os.environ[EVENT_LOG_ENV].strip()))
