"""The program's own spans of a traced advisor window, on the trace's clock.

The program keeps its spans in memory (``repro.obs.records()``, clock
``time.perf_counter_ns``); the trace's host events are only the
benchmark's ``bench.`` spans (``trace.load_events``).  The two are put
on one clock through the advisor's variables: each window variable is a
``bench.variable`` span of the driver around exactly one
``repro.advise.variable`` span of the program.

:func:`window_spans` takes the last N ``repro.advise.variable`` records,
N the number of ``bench.variable`` spans starting in the window (the
set-up variable comes before them), with their descendants and the
root spans of other threads (the stream's reader) that lie inside their
interval, and shifts them all by the offset of the first pair.  It
returns None where the program keeps no spans (``repro.obs`` missing),
where the window has no variable or the log fewer variables than the
window, and where a shifted variable reaches more than
``TOLERANCE_NS`` outside its ``bench.variable``.
"""
from __future__ import annotations

import collections
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

VARIABLE = "repro.advise.variable"
BENCH_VARIABLE = "bench.variable"
TOLERANCE_NS = 1e6


class Span(NamedTuple):
    name: str
    index: int
    parent: Optional[int]
    thread: int
    start_ns: float               # on the trace's clock
    end_ns: float
    attrs: dict
    error: bool
    variable: int                 # which of the window's variables

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns


def program_records() -> Optional[list]:
    """The program's span log, or None where it keeps none."""
    try:
        from repro import obs
    except ImportError:
        return None
    return obs.records()


def window_spans(ctx, records: Optional[Sequence] = None
                 ) -> Optional[List[Span]]:
    """The window's variables and the spans under them, shifted onto the
    trace's clock (see the module's docstring); ``records`` defaults to
    :func:`program_records`."""
    if records is None:
        records = program_records()
        if records is None:
            return None
    TR = ctx.lib("trace")
    lo, hi = ctx.window
    marks = [e for e in TR.spans(ctx.events, BENCH_VARIABLE)
             if lo <= e.start_ns < hi]
    variables = sorted((r for r in records if r.name == VARIABLE),
                       key=lambda r: r.index)
    n = len(marks)
    if n == 0 or len(variables) < n:
        return None
    variables = variables[-n:]
    shift = marks[0].start_ns - variables[0].start_ns
    for m, v in zip(marks, variables):
        if (v.start_ns + shift < m.start_ns - TOLERANCE_NS
                or v.end_ns + shift > m.end_ns + TOLERANCE_NS):
            return None
    children = collections.defaultdict(list)
    for r in records:
        if r.parent is not None:
            children[r.parent].append(r)
    out: List[Span] = []
    for i, v in enumerate(variables):
        todo = [v]
        todo += [r for r in records if r.parent is None
                 and r.thread != v.thread
                 and v.start_ns <= r.start_ns and r.end_ns <= v.end_ns]
        while todo:
            r = todo.pop()
            out.append(Span(r.name, r.index, r.parent, r.thread,
                            r.start_ns + shift, r.end_ns + shift,
                            dict(r.attrs), r.error, i))
            todo += children.get(r.index, ())
    out.sort(key=lambda s: s.index)
    return out


def named(spans: Sequence[Span], name: str) -> List[Span]:
    return [s for s in spans if s.name == name]


def total_ns(spans: Sequence[Span], name: str) -> float:
    return float(sum(s.dur_ns for s in spans if s.name == name))


def idle_intervals(TR, events, plane: str, lo: float,
                   hi: float) -> List[Tuple[float, float]]:
    """Where no operation ran on ``plane`` inside [lo, hi): the
    complement of ``trace.busy_intervals``."""
    edges = [lo] + [t for ab in TR.busy_intervals(events, plane, lo, hi)
                    for t in ab] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def _overlaps(a: Sequence[tuple], b: Sequence[tuple]):
    """``(i, length)`` of each overlap of ``a[i]`` with an interval of
    ``b``; both sorted lists of disjoint intervals (extra items of
    ``a``'s tuples are ignored)."""
    i = j = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            yield i, hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1


def overlap_ns(a: Sequence[tuple], b: Sequence[tuple]) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    return float(sum(n for _, n in _overlaps(a, b)))


def innermost(spans: Sequence[Span]) -> List[Tuple[float, float, str]]:
    """Disjoint ``(start, end, name)`` pieces of properly nested spans of
    one thread, each piece named by the innermost span covering it."""
    out: List[Tuple[float, float, str]] = []
    stack: List[Span] = []
    t = None

    def emit(until: float) -> None:
        if stack and until > t:
            out.append((t, until, stack[-1].name))

    for s in sorted(spans, key=lambda s: (s.start_ns, -s.end_ns)):
        while stack and stack[-1].end_ns <= s.start_ns:
            emit(stack[-1].end_ns)
            t = stack.pop().end_ns
        emit(s.start_ns)
        stack.append(s)
        t = s.start_ns
    while stack:
        emit(stack[-1].end_ns)
        t = stack.pop().end_ns
    return out


def idle_by_span(ctx, spans: Sequence[Span],
                 plane: str) -> Dict[str, float]:
    """Device idle seconds of ``plane`` inside the window's variables,
    by the innermost span of the advisor's own thread open at the time
    (a variable's name stands for its self time)."""
    TR = ctx.lib("trace")
    idle = idle_intervals(TR, ctx.events, plane, *ctx.window)
    acc: Dict[str, float] = collections.Counter()
    for v in named(spans, VARIABLE):
        pieces = innermost([s for s in spans if s.variable == v.variable
                            and s.thread == v.thread])
        for i, n in _overlaps(pieces, idle):
            acc[pieces[i][2]] += n / 1e9
    return dict(acc)
