"""Reduce a profiler trace to the benchmark's device numbers.

The trace is first flattened into events ``(plane, line, name, start_ns,
dur_ns)`` (:func:`load_events` reads a ``.xplane.pb``; the tests feed a
small recorded list).  Everything else works on that list:

* device planes are ``/device:TPU:<i>``; their ``XLA Ops`` line holds
  one event per executed operation and their ``XLA Modules`` line one
  event per executable launch, named ``jit_<function>(<id>)``;
* host spans are the benchmark's own ``TraceAnnotation``s, on the host
  plane, named ``bench.<what>``;
* busy time is the union of operation intervals on a device, inside a
  span (the window); the idle share is 1 - busy / span length,
  averaged over the devices used;
* module time is the summed device time of one executable's launches;
* ``breakdown`` lists the operations that took most device time and the
  longest idle gaps, each named by the innermost host span covering it.
"""
from __future__ import annotations

import collections
import re
from typing import Iterable, List, NamedTuple, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def op_name(text: str) -> str:
    """An operation's short name: the HLO value name before `` = ``, with
    a custom call's target (``%custom-call.26 EighTpu``)."""
    name = text.split(" = ", 1)[0]
    target = _TARGET.search(text)
    return f"{name} {target.group(1)}" if target else name


def load_events(path: str) -> List[Event]:
    """Device op and module events and the benchmark's host spans of one
    ``.xplane.pb`` file (other host events are left out; operation names
    are shortened by :func:`op_name`)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        dev = DEVICE_PLANE.match(plane.name)
        host = plane.name == HOST_PLANE
        if not (dev or host):
            continue
        for line in plane.lines:
            if dev and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if host and not ev.name.startswith(SPAN_PREFIX):
                    continue
                name = op_name(ev.name) if line.name == OPS_LINE else ev.name
                out.append(Event(plane.name, line.name, name,
                                 float(ev.start_ns), float(ev.duration_ns)))
    return out


def devices(events: Iterable[Event]) -> List[str]:
    return sorted({e.plane for e in events if DEVICE_PLANE.match(e.plane)},
                  key=lambda p: int(DEVICE_PLANE.match(p).group(1)))


def spans(events: Iterable[Event], name: str) -> List[Event]:
    return sorted((e for e in events
                   if e.plane == HOST_PLANE and e.name == name),
                  key=lambda e: e.start_ns)


def _clip(events, lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(e.start_ns, lo), min(e.end_ns, hi)) for e in events
            if e.end_ns > lo and e.start_ns < hi]


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted intervals."""
    out: List[list] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_intervals(events, plane: str, lo: float, hi: float):
    ops = [e for e in events if e.plane == plane and e.line == OPS_LINE]
    return union(_clip(ops, lo, hi))


def busy_s(events, plane: str, lo: float, hi: float) -> float:
    return sum(b - a for a, b in busy_intervals(events, plane, lo, hi)) / 1e9


def window_bounds(events, name: str = "bench.window") -> Tuple[float, float]:
    """(start, end) ns of the one span called ``name``."""
    w = spans(events, name)
    if len(w) != 1:
        raise ValueError(f"expected one {name!r} span, found {len(w)}")
    return w[0].start_ns, w[0].end_ns


def device_summary(events, planes: Sequence[str], lo: float,
                   hi: float) -> dict:
    """``busy_s`` averaged over ``planes`` and the window length."""
    busy = [busy_s(events, p, lo, hi) for p in planes]
    return {"busy_s": sum(busy) / max(len(busy), 1),
            "window_s": (hi - lo) / 1e9}


def idle_share(events, planes: Sequence[str], lo: float,
               hi: float) -> Optional[float]:
    """Percent of the window in which no operation ran, averaged over
    ``planes``; None without a device plane."""
    if not planes:
        return None
    s = device_summary(events, planes, lo, hi)
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])


def module_launches(events, plane: str, pattern: str, lo: float = float("-inf"),
                    hi: float = float("inf")) -> List[Event]:
    """Launch events of the executables whose name matches ``pattern``
    (a regular expression), starting inside [lo, hi)."""
    rx = re.compile(pattern)
    return [e for e in events
            if e.plane == plane and e.line == MODULES_LINE
            and rx.search(e.name) and lo <= e.start_ns < hi]


def mean_launch_ms(events, planes: Sequence[str], pattern: str,
                   lo: float = float("-inf"),
                   hi: float = float("inf")) -> Optional[float]:
    """Mean device time per launch of the matching executable on the
    busiest of ``planes``; None where no launch is found."""
    best = None
    for p in planes:
        evs = module_launches(events, p, pattern, lo, hi)
        if not evs:
            continue
        total = sum(e.dur_ns for e in evs)
        if best is None or total > best[0]:
            best = (total, len(evs))
    return None if best is None else best[0] / best[1] / 1e6


def self_times(events, plane: str, lo: float, hi: float) -> collections.Counter:
    """Device seconds per operation name inside the window, each
    operation counted without the operations nested in it (a loop's
    body runs as operations inside the loop's own event)."""
    ops = sorted((e for e in events if e.plane == plane
                  and e.line == OPS_LINE and lo <= e.start_ns < hi),
                 key=lambda e: (e.start_ns, -e.dur_ns))
    acc: collections.Counter = collections.Counter()
    stack: List[list] = []                  # [event, time of children]
    for e in ops:
        while stack and stack[-1][0].end_ns <= e.start_ns:
            done, kids = stack.pop()
            acc[done.name] += (done.dur_ns - kids) / 1e9
        if stack:
            stack[-1][1] += e.dur_ns
        stack.append([e, 0.0])
    for done, kids in stack:
        acc[done.name] += (done.dur_ns - kids) / 1e9
    return acc


def top_ops(events, planes: Sequence[str], lo: float, hi: float,
            n: int = 10) -> List[list]:
    """The ``n`` operation names with most device self seconds (averaged
    over ``planes``) inside the window."""
    acc: collections.Counter = collections.Counter()
    for p in planes:
        for k, v in self_times(events, p, lo, hi).items():
            acc[k] += v / len(planes)
    return [[k, v] for k, v in acc.most_common(n)]


def idle_gaps(events, plane: str, lo: float, hi: float,
              n: int = 10) -> List[list]:
    """The ``n`` longest idle gaps of ``plane`` inside the window, each
    named by the innermost benchmark span that covers its middle."""
    busy = busy_intervals(events, plane, lo, hi)
    edges = [lo] + [t for ab in busy for t in ab] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    host = [e for e in events if e.plane == HOST_PLANE
            and e.name != "bench.window"]
    out = []
    for a, b in gaps[:n]:
        mid = (a + b) / 2
        cover = [e for e in host if e.start_ns <= mid < e.end_ns]
        name = (min(cover, key=lambda e: e.dur_ns).name if cover
                else "no benchmark span")
        out.append([name, (b - a) / 1e9])
    return out


def breakdown(events, planes: Sequence[str], lo: float, hi: float) -> dict:
    if not planes:
        return {"device_ops": [], "idle_gaps": []}
    return {"device_ops": top_ops(events, planes, lo, hi),
            "idle_gaps": idle_gaps(events, planes[0], lo, hi)}


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peaks: dict) -> Tuple[float, str]:
    """(percent of the least time the chip could take, which bound sets
    it: "compute" or "memory")."""
    t_c = flops / peaks["flops_per_s"]
    t_m = nbytes / peaks["bytes_per_s"]
    return 100.0 * max(t_c, t_m) / seconds, ("compute" if t_c >= t_m
                                            else "memory")
