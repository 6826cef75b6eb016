"""The program's span log (``repro.obs``): nesting, threads, the bounded
log, attributes, spans that raise, and that a span is a host event of a
profiler trace (the clock the device's operations are on)."""
import collections
import glob
import sys
import threading

import pytest

from repro import obs


def _since(mark: obs.Span) -> list:
    return [r for r in obs.records() if r.index > mark.index]


def _mark() -> obs.Span:
    with obs.span("test.mark") as m:
        pass
    return m


def test_nesting_gives_parent_indices():
    mark = _mark()
    with obs.span("a") as a:
        with obs.span("b") as b:
            with obs.span("c") as c:
                pass
        with obs.span("d") as d:
            pass
    got = _since(mark)
    assert [r.name for r in got] == ["c", "b", "d", "a"]   # in end order
    assert a.parent is None
    assert b.parent == d.parent == a.index
    assert c.parent == b.index
    assert a.index < b.index < c.index < d.index
    assert a.start_ns <= b.start_ns <= c.start_ns <= c.end_ns <= b.end_ns
    assert b.end_ns <= d.start_ns <= d.end_ns <= a.end_ns
    assert {r.thread for r in got} == {threading.get_ident()}
    assert all(r.duration_ns >= 0 and not r.error for r in got)


def test_a_second_thread_has_its_own_roots():
    mark = _mark()
    seen = {}

    def body():
        with obs.span("t.outer") as o:
            with obs.span("t.inner"):
                pass
        seen["outer"] = o

    with obs.span("main") as main:
        t = threading.Thread(target=body)
        t.start()
        t.join(timeout=10)
    assert not t.is_alive()
    got = {r.name: r for r in _since(mark)}
    assert got["t.outer"].parent is None
    assert got["t.inner"].parent == got["t.outer"].index
    assert got["t.outer"].thread == got["t.inner"].thread != main.thread
    assert main.parent is None


def test_the_bounded_log_drops_the_oldest(monkeypatch):
    monkeypatch.setattr(obs, "_log", collections.deque(maxlen=3))
    for i in range(5):
        with obs.span(f"s{i}"):
            pass
    assert [r.name for r in obs.records()] == ["s2", "s3", "s4"]
    assert obs.LOG_SIZE == 65536


def test_attributes_set_in_the_body_are_kept():
    with obs.span("x", rows=3) as sp:
        sp.attrs["bytes"] = 12
    assert obs.records()[-1] is sp
    assert sp.attrs == {"rows": 3, "bytes": 12}


def test_a_span_that_raises_is_recorded():
    mark = _mark()
    with pytest.raises(ValueError):
        with obs.span("outer") as outer:
            with obs.span("failing"):
                raise ValueError("boom")
    got = {r.name: r for r in _since(mark)}
    assert got["failing"].error and got["outer"].error
    assert got["failing"].parent == outer.index
    with obs.span("after") as after:            # the stack was unwound
        pass
    assert after.parent is None and not after.error


def test_threads_share_the_log_without_losing_a_span():
    """More threads than cores, each opening nested spans with a short
    switch interval: every span is logged once, under its own parent."""
    mark = _mark()
    n_threads, per = 24, 200
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def body(k):
            for i in range(per):
                with obs.span(f"w{k}") as o:
                    with obs.span(f"w{k}.in"):
                        pass

        ts = [threading.Thread(target=body, args=(k,))
              for k in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in ts)
    finally:
        sys.setswitchinterval(old)
    got = _since(mark)
    assert len(got) == 2 * n_threads * per
    assert len({r.index for r in got}) == len(got)
    by_index = {r.index: r for r in got}
    for r in got:
        if r.name.endswith(".in"):
            p = by_index[r.parent]
            assert p.name == r.name[:-3] and p.thread == r.thread
        else:
            assert r.parent is None


def test_a_span_is_a_host_event_of_the_profiler_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    with jax.profiler.trace(str(tmp_path)):
        with obs.span("repro.test", rows=1):
            jnp.ones(8).sum().block_until_ready()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    names = [ev.name for plane in data.planes if plane.name == "/host:CPU"
             for line in plane.lines for ev in line.events]
    assert names.count("repro.test") == 1
