"""Spans of the fabric simulator's host path.

``span(name, **attrs)`` marks one stretch of host work twice:

* it enters a ``jax.profiler.TraceAnnotation`` of the same name, so a
  profiler trace holds the span beside the device's operations (only when
  JAX is already imported: no trace can run in a process without it);
* on exit it appends one :class:`Record` to a bounded ring in memory,
  timed on ``time.perf_counter_ns()``.

Spans nest per thread.  A span opened outside any other is a *root*; every
span under it carries the root's ``id`` as its ``root_id`` (one call of
``run_sweep`` is one root).  The object a span yields takes counters,
``add(key, n)``, which land in the record's ``attrs``.

The ring is always on and holds the newest :data:`MAXLEN` records;
:func:`records` returns a snapshot of it.  Readers (the benchmark's
per-layer metrics) take the records whose interval lies inside the window
they measured.
"""
from __future__ import annotations

import collections
import itertools
import sys
import threading
import time
from contextlib import contextmanager, nullcontext
from typing import NamedTuple

__all__ = ["MAXLEN", "Record", "records", "span"]

MAXLEN = 4096


class Record(NamedTuple):
    id: int
    parent_id: int | None
    root_id: int
    name: str
    t0_ns: int
    t1_ns: int
    attrs: dict


class Span:
    """An open span: its identifiers and the attributes its record will
    carry."""
    __slots__ = ("id", "parent_id", "root_id", "attrs")

    def __init__(self, parent: "Span | None", attrs: dict):
        self.id = next(_ids)
        self.parent_id = parent.id if parent is not None else None
        self.root_id = parent.root_id if parent is not None else self.id
        self.attrs = attrs

    def add(self, key: str, n) -> None:
        """Count ``n`` more of ``key`` on this span."""
        self.attrs[key] = self.attrs.get(key, 0) + n


_ring: collections.deque = collections.deque(maxlen=MAXLEN)
_ids = itertools.count(1)
_open = threading.local()


def _annotation(name: str):
    jax = sys.modules.get("jax")
    return (jax.profiler.TraceAnnotation(name) if jax is not None
            else nullcontext())


@contextmanager
def span(name: str, **attrs):
    stack = _open.__dict__.setdefault("stack", [])
    s = Span(stack[-1] if stack else None, attrs)
    stack.append(s)
    t0 = time.perf_counter_ns()
    try:
        with _annotation(name):
            yield s
    finally:
        t1 = time.perf_counter_ns()
        stack.pop()
        _ring.append(Record(s.id, s.parent_id, s.root_id, name, t0, t1,
                            s.attrs))


def records() -> list[Record]:
    """The ring's records, oldest first (in the order the spans closed)."""
    return list(_ring)
