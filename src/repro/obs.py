"""Spans of the program's own work, for the profiler and for readers in
the process.

``with span("serve.batch", batch=3):`` does two things:

- it enters ``jax.profiler.TraceAnnotation(name, **attrs)``, so the span
  lands, with its attributes, in any profiler trace taken meanwhile, on
  the device trace's clock (TensorBoard, Perfetto and a kept
  ``.xplane.pb`` all show it);
- it appends a :class:`Span` record, timed by ``time.perf_counter_ns()``,
  to a process-wide ring of :data:`RING_SIZE` records, which
  :func:`spans` copies out.

Recording is always on: about 2.5 us a span on a TPU v5e host with the
profiler off. The ring is bounded, so a long-running server keeps only
its newest spans.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import NamedTuple

import jax

#: records the ring keeps; the oldest are dropped first
RING_SIZE = 1 << 15


class Span(NamedTuple):
    """One finished span. ``parent`` is the ``id`` of the innermost span
    that was open around it on the same thread, None at the top."""
    name: str
    start_ns: int               # time.perf_counter_ns() at entry
    end_ns: int                 # and at exit
    parent: int | None
    attrs: dict
    id: int


_ring: collections.deque[Span] = collections.deque(maxlen=RING_SIZE)
_ids = itertools.count()
_open = threading.local()       # .stack: ids of the thread's open spans


class span:
    """Context manager: one host span named ``name`` with ``attrs``."""

    __slots__ = ("name", "attrs", "id", "parent", "start_ns", "_note")

    def __init__(self, name: str, **attrs):
        self.name, self.attrs = name, attrs

    def __enter__(self):
        stack = _open.__dict__.setdefault("stack", [])
        self.id = next(_ids)
        self.parent = stack[-1] if stack else None
        stack.append(self.id)
        self._note = jax.profiler.TraceAnnotation(self.name, **self.attrs)
        self._note.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter_ns()
        self._note.__exit__(*exc)
        _open.stack.pop()
        _ring.append(Span(self.name, self.start_ns, end, self.parent,
                          self.attrs, self.id))
        return False


def spans() -> list[Span]:
    """A copy of the ring's records, oldest first (by end time)."""
    return list(_ring)

