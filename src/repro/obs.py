"""Host spans at the program's layer boundaries, on the profiler's clock.

A ``span`` marks one step of host code: the sweep executor's prepare,
dispatch, pull and materialize of a chunk, the Study's column fill, the
control loop's tick and the steps inside it.  The switch is the profiler
session itself: while ``jax.profiler`` traces (``jax.profiler.trace(dir)``
or ``start_trace``/``stop_trace`` around a Study or a loop), each span

* enters a ``jax.profiler.TraceAnnotation`` of its name, so it shows on
  the host plane's python line beside JAX's dispatches and on the same
  clock as the device's operations, and
* is kept in memory as a ``SpanRecord``: start and end
  (``time.perf_counter``), its id, its parent's (the enclosing span on
  the same thread), its trace id, its attributes, the XLA compiles that
  ran inside it, and the type of an exception that left it.

``spans()`` returns what was kept and ``clear()`` forgets it.  Nothing is
written to disk: the profiler's own trace is the only exporter.  Outside
a session a span records nothing; it still reads the clock on entry and
exit, so ``duration_s`` is there for callers that report it.
"""
from __future__ import annotations

import dataclasses
import itertools
import threading
import time
from typing import Dict, List, Optional, Tuple

import jax
from jax.profiler import TraceAnnotation

#: spans kept at most; later ones are counted in ``Snapshot.dropped``
MAX_SPANS = 100_000

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclasses.dataclass
class SpanRecord:
    name: str
    start: float                 # time.perf_counter() seconds
    end: float
    span_id: int
    parent_id: Optional[int]     # the enclosing span on the same thread
    trace_id: int
    attrs: Dict
    compiles: int = 0            # XLA compiles inside, children's included
    compile_s: float = 0.0
    own_compiles: int = 0        # those with no child span around them
    own_compile_s: float = 0.0
    error: Optional[str] = None  # type of the exception that left the span

    @property
    def duration_s(self) -> float:
        return self.end - self.start


@dataclasses.dataclass(frozen=True)
class Snapshot:
    spans: Tuple[SpanRecord, ...]   # in the order they ended
    dropped: int                    # spans not kept: the buffer was full


_ids = itertools.count(1)
_local = threading.local()
_lock = threading.Lock()
_buffer: List[SpanRecord] = []
_dropped = 0


def _stack() -> List[SpanRecord]:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def new_trace_id() -> int:
    """An identifier for spans that belong together without a common
    parent span, such as all the ticks of one control loop."""
    return next(_ids)


class span:
    """``with span(name, **attrs) as sp:`` around host code; ``trace_id=``
    puts a span with no parent into a caller's trace.  ``sp.attrs`` may
    gain entries inside the body; ``sp.duration_s`` holds after it."""

    __slots__ = ("name", "attrs", "trace_id", "start", "end", "_rec", "_ann")

    def __init__(self, name: str, trace_id: Optional[int] = None, **attrs):
        self.name, self.trace_id, self.attrs = name, trace_id, attrs
        self._rec = None

    def __enter__(self) -> "span":
        if TraceAnnotation.is_enabled():
            stack = _stack()
            parent = stack[-1] if stack else None
            trace = (self.trace_id if self.trace_id is not None
                     else parent.trace_id if parent is not None
                     else new_trace_id())
            self._rec = SpanRecord(
                self.name, 0.0, 0.0, next(_ids),
                None if parent is None else parent.span_id, trace,
                self.attrs)
            stack.append(self._rec)
            self._ann = TraceAnnotation(self.name)
            self._ann.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.end = time.perf_counter()
        rec = self._rec
        if rec is None:
            return
        self._ann.__exit__(exc_type, exc, tb)
        rec.start, rec.end = self.start, self.end
        if exc_type is not None:
            rec.error = exc_type.__name__
        stack = _stack()
        for i in range(len(stack) - 1, -1, -1):
            if stack[i] is rec:
                del stack[i]
                break
        _keep(rec)

    @property
    def duration_s(self) -> float:
        return self.end - self.start


def _keep(rec: SpanRecord) -> None:
    global _dropped
    with _lock:
        if len(_buffer) < MAX_SPANS:
            _buffer.append(rec)
        else:
            _dropped += 1


def spans() -> Snapshot:
    """The spans recorded since the last ``clear``."""
    with _lock:
        return Snapshot(tuple(_buffer), _dropped)


def clear() -> None:
    global _dropped
    with _lock:
        _buffer.clear()
        _dropped = 0


def _on_duration(event: str, duration_secs: float, **_) -> None:
    """A compile counts in every open span of the compiling thread, and
    as its own in the innermost."""
    if event != _COMPILE_EVENT:
        return
    stack = getattr(_local, "stack", None)
    if not stack:
        return
    for rec in stack:
        rec.compiles += 1
        rec.compile_s += duration_secs
    stack[-1].own_compiles += 1
    stack[-1].own_compile_s += duration_secs


jax.monitoring.register_event_duration_secs_listener(_on_duration)
