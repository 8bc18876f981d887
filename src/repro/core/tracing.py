"""Spans: named, timed stretches of the program's own work.

``span(name, **attrs)`` is a context manager that does two things:

* it opens a ``jax.profiler.TraceAnnotation`` of the same name, so that
  under a profiler trace the span lies on the host plane, on the thread that
  ran it and on the device planes' time base;
* when it closes, it records a ``Span`` into one bounded in-memory buffer
  that ``spans()`` reads: start and end on ``time.monotonic()`` (the clock of
  the ``EventLog``), the thread, its id and the id of the span that encloses
  it on the same thread, and its attrs.

A span inherits the attrs of the span that encloses it on its thread, so
the spans inside a ``train.step`` carry its ``exec_id``, ``attempt`` and
``step``. There is no switch: the buffer holds the newest ``MAX_SPANS``
spans, and a span with the profiler off costs a few microseconds.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Any, NamedTuple

import jax

MAX_SPANS = 65536

_buffer: collections.deque = collections.deque(maxlen=MAX_SPANS)
_lock = threading.Lock()
_ids = itertools.count(1)
_local = threading.local()


class Span(NamedTuple):
    name: str
    start: float                  # time.monotonic()
    end: float
    thread: str
    span_id: int
    parent_id: int | None         # the enclosing span on the same thread
    attrs: dict[str, Any]

    @property
    def duration(self) -> float:
        return self.end - self.start


class span:
    """``with span("train.step", step=s): ...`` (see the module doc)."""

    __slots__ = ("name", "attrs", "_id", "_parent_id", "_start", "_trace")

    def __init__(self, name: str, **attrs: Any):
        self.name = name
        self.attrs = attrs

    def __enter__(self) -> span:
        stack = _local.__dict__.setdefault("stack", [])
        self._parent_id = None
        if stack:
            parent = stack[-1]
            self._parent_id = parent._id
            self.attrs = {**parent.attrs, **self.attrs}
        self._id = next(_ids)
        stack.append(self)
        self._trace = jax.profiler.TraceAnnotation(self.name)
        self._trace.__enter__()
        self._start = time.monotonic()
        return self

    def __exit__(self, *exc) -> None:
        end = time.monotonic()
        self._trace.__exit__(*exc)
        _local.stack.pop()
        record = Span(self.name, self._start, end,
                      threading.current_thread().name, self._id,
                      self._parent_id, self.attrs)
        with _lock:
            _buffer.append(record)


def spans(prefix: str | None = None, since: float | None = None) -> list[Span]:
    """The recorded spans, oldest first: those whose name starts with
    ``prefix`` and that started at or after ``since`` (monotonic seconds)."""
    with _lock:
        out = list(_buffer)
    return [s for s in out
            if (prefix is None or s.name.startswith(prefix))
            and (since is None or s.start >= since)]
