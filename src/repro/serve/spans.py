"""Host spans of the serving program, on the device trace's clock.

``span(name, **attrs)`` is a context manager that does two things:

* it stamps ``time.perf_counter()`` at entry and exit and appends a
  ``Span(name, start, end, parent, id, attrs)`` to a bounded ring that
  is always on (``RING_SIZE`` entries; the oldest fall out first);
* it opens ``jax.profiler.TraceAnnotation(name)``, so inside a profiler
  session the same span sits on the trace's host plane, on the clock of
  the device's events, and an idle gap of the device can be put down to
  the span that covers it.

The ring holds host ints, floats, strings and tuples only — never a
device value — so recording a span costs no device sync.  ``attrs`` is
the span's own dict: code inside the span fills in what it learns (the
lanes a dispatch carried, the requests an admission took).  Parents
come from a per-thread stack of open spans; a span opened on another
thread (an executor's worker) names its parent explicitly.

Spans recorded after the fact (``record``) carry stamps taken earlier
(``serve.request`` is written when a request finishes) and have no
annotation on the profiler's trace.

A reader keeps the spans of a time window with ``between(t0, t1)``.
When the ring is full and ``earliest_start()`` is later than ``t0``, the
ring may have lost the window's beginning, and a sum over the window
would be short: ``between`` returns None then, and readers report
nothing.

Names, and what reads each, are listed in PERF.md (section 3).
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Any, NamedTuple

from jax.profiler import TraceAnnotation

RING_SIZE = 1 << 17


class Span(NamedTuple):
    name: str
    start: float            # time.perf_counter() seconds
    end: float
    parent: int | None      # id of the enclosing span
    id: int
    attrs: dict


class _Open:
    """One span while it is open (``Recorder.span``)."""

    __slots__ = ("_rec", "_stack", "_note", "name", "attrs", "parent", "id",
                 "start", "end")

    def __init__(self, rec: "Recorder", name: str, parent: int | None,
                 attrs: dict):
        self._rec = rec
        self.name = name
        self.attrs = attrs
        self.parent = parent
        self.start = self.end = 0.0

    def __enter__(self) -> "_Open":
        rec = self._rec
        self.id = next(rec._ids)
        stack = self._stack = rec._stack()
        if self.parent is None and stack:
            self.parent = stack[-1]
        stack.append(self.id)
        # Outside a profiler session an annotation records nothing: skip
        # making one (most of a span's cost).
        self._note = TraceAnnotation(self.name) \
            if TraceAnnotation.is_enabled() else None
        if self._note is not None:
            self._note.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.end = time.perf_counter()
        if self._note is not None:
            self._note.__exit__(*exc)
        self._stack.pop()
        self._rec._ring.append((self.name, self.start, self.end,
                                self.parent, self.id, self.attrs))


class Recorder:
    """A bounded ring of finished spans (plain tuples in ``Span`` field
    order, made ``Span`` when read)."""

    def __init__(self, size: int = RING_SIZE):
        self._ring: collections.deque = collections.deque(maxlen=size)
        self._ids = itertools.count(1)
        self._local = threading.local()

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def span(self, name: str, *, parent: int | None = None,
             **attrs: Any) -> _Open:
        return _Open(self, name, parent, attrs)

    def record(self, name: str, began: float, ended: float,
               **attrs: Any) -> None:
        """A finished span from stamps taken earlier (no annotation)."""
        self._ring.append((name, began, ended, None, next(self._ids),
                           attrs))

    def held(self) -> list[Span]:
        return [Span(*t) for t in self._ring]

    def earliest_start(self) -> float | None:
        """The earliest start the ring still holds (None when empty)."""
        return min((t[1] for t in self._ring), default=None)

    def lost_since(self, t0: float) -> bool:
        """Whether spans that began at ``t0`` or later may have fallen
        out: the ring is full and holds nothing that began before."""
        return len(self._ring) == self._ring.maxlen \
            and self.earliest_start() > t0

    def between(self, t0: float, t1: float) -> list[Span] | None:
        """Spans wholly inside ``[t0, t1]``, in the order they ended;
        None when the ring may have lost some (``lost_since``)."""
        if self.lost_since(t0):
            return None
        return [Span(*t) for t in self._ring if t[1] >= t0 and t[2] <= t1]

    def clear(self) -> None:
        self._ring.clear()


RECORDER = Recorder()
span = RECORDER.span
record = RECORDER.record
