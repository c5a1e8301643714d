"""Host spans of the program, on the profiler's clock.

``span(name, **attrs)`` marks a phase of the program's own work. Each span
is two things at once:

- a ``jax.profiler.TraceAnnotation``, so any profiler trace of the process
  (XProf, TensorBoard, ``jax.profiler.trace``) shows it on the host line,
  nested and on the same clock as the device's operations; with no
  profiler active the annotation costs next to nothing;
- a record in a bounded, process-wide, in-memory log: id, parent id (the
  span open on the same thread when it began), name, start and end in
  ``time.time_ns()`` (the clock the profiler stamps host events with),
  attrs, and outcome (``"ok"``, or the type name of the exception that
  left the span; the exception still propagates).

``spans()`` returns a copy of the log, oldest first by end; ``clear()``
empties it. Records beyond ``LOG_SIZE`` push out the oldest.

Inside the ``with`` block the span object is at hand: code may add attrs
it learns only as the work goes on (they reach the log, not the profiler's
event, which took the attrs given at entry), or name the outcome itself,
as the trainer does for a compile that runs out of memory.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import List, NamedTuple, Optional

import jax

#: records the log holds before the oldest is dropped
LOG_SIZE = 16384


class Record(NamedTuple):
    id: int
    parent_id: Optional[int]
    name: str
    t0_ns: int
    t1_ns: int
    attrs: dict
    outcome: str

    @property
    def seconds(self) -> float:
        return (self.t1_ns - self.t0_ns) / 1e9


_log: "collections.deque[Record]" = collections.deque(maxlen=LOG_SIZE)
_ids = itertools.count(1)
_local = threading.local()


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class span:
    """Context manager: one span of the program's work (module docstring).
    """
    __slots__ = ("name", "attrs", "outcome", "_annotation", "_id",
                 "_parent", "_t0")

    def __init__(self, name: str, **attrs):
        self.name, self.attrs, self.outcome = name, attrs, None
        self._annotation = jax.profiler.TraceAnnotation(name, **attrs)

    def __enter__(self) -> "span":
        stack = _stack()
        self._parent = stack[-1] if stack else None
        self._id = next(_ids)
        stack.append(self._id)
        self._t0 = time.time_ns()
        self._annotation.__enter__()
        return self

    def __exit__(self, typ, exc, tb) -> bool:
        self._annotation.__exit__(typ, exc, tb)
        t1 = time.time_ns()
        _stack().pop()
        if self.outcome is None:
            self.outcome = "ok" if typ is None else typ.__name__
        _log.append(Record(self._id, self._parent, self.name, self._t0, t1,
                           dict(self.attrs), self.outcome))
        return False


class step_span(span):
    """A span that is also the profiler's step marker
    (``jax.profiler.StepTraceAnnotation``), so XProf's and TensorBoard's
    step views group the trace by ``step_num``."""
    __slots__ = ()

    def __init__(self, name: str, step_num: int, **attrs):
        self.name, self.outcome = name, None
        self.attrs = dict(step_num=step_num, **attrs)
        self._annotation = jax.profiler.StepTraceAnnotation(
            name, step_num=step_num, **attrs)


def spans() -> List[Record]:
    """A copy of the log, oldest first (by end)."""
    return list(_log)


def clear() -> None:
    _log.clear()
