"""Host spans of the program, kept in memory and shown to the profiler.

    from repro import obs

    with obs.span("repro.compress.run", compressor=name) as sp:
        ...
        sp.attrs["bytes"] = size          # facts known only at the end

Each span does two things:

* it enters ``jax.profiler.TraceAnnotation(name)``, so that while a
  profiler runs the span is a host event of the trace, on the clock of
  the device's operations (the name only: attributes are never
  formatted for the profiler);
* when it ends, its record (:class:`Span`: name, index, parent index,
  thread, ``perf_counter_ns`` start and end, attributes, whether it
  ended in an exception) is appended to a bounded in-process log that
  :func:`records` returns.  The oldest records fall out past
  :data:`LOG_SIZE`.

A span's parent is the innermost span open on the same thread when it
starts; a span opened on a thread with none open is a root.  Counts of
work are attributes of the span that did the work (``rows``,
``bytes``), not a registry of their own.  Recording is always on; it
costs a ``TraceAnnotation``, two clock reads and an append.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

LOG_SIZE = 65536

_log: "collections.deque[Span]" = collections.deque(maxlen=LOG_SIZE)
_index = itertools.count()        # next() is atomic under the GIL
_local = threading.local()


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class Span:
    """One span: the context manager and, once it has ended, the
    record.  ``index`` is unique in the process and increases with the
    start; ``parent`` is the parent's ``index`` (None for a root)."""

    __slots__ = ("name", "attrs", "index", "parent", "thread", "start_ns",
                 "end_ns", "error", "_annotation")

    def __init__(self, name: str, attrs: Dict[str, Any]):
        self.name = name
        self.attrs = attrs
        self.index = -1
        self.parent: Optional[int] = None
        self.thread = 0
        self.start_ns = self.end_ns = 0
        self.error = False
        self._annotation = None

    def __enter__(self) -> "Span":
        stack = _stack()
        self.parent = stack[-1].index if stack else None
        self.index = next(_index)
        self.thread = threading.get_ident()
        stack.append(self)
        self._annotation = TraceAnnotation(self.name)
        self._annotation.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, typ, value, tb) -> bool:
        self.end_ns = time.perf_counter_ns()
        self._annotation.__exit__(typ, value, tb)
        self._annotation = None
        self.error = typ is not None
        _stack().pop()
        _log.append(self)
        return False

    @property
    def duration_ns(self) -> int:
        return self.end_ns - self.start_ns


def span(name: str, **attrs) -> Span:
    """A span called ``name`` with the given attributes; use it as
    ``with span(...) as sp:``."""
    return Span(name, attrs)


def records() -> List[Span]:
    """The ended spans still in the log, oldest first (a copy)."""
    return list(_log)
