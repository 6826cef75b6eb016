"""CPU tests of the readers of the program's own spans
(``bench/program_spans.py`` and the four metrics that use it): the
shift onto the trace's clock, the choice of the window's variables,
refusal where the two do not match, the numbers on a synthetic trace
worked by hand, and a traced rehearsal of ``hurricane-advise``."""
from __future__ import annotations

import importlib.util
import json
import os
import sys
from typing import NamedTuple, Optional

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
BENCH = os.path.join(REPO, "bench")
NEW = ("advise_train_share", "compress_run_ms", "stream_pad_share",
       "idle_in_train.advise")
MS = 1e6                      # ns
T0 = 7e9                      # the trace's clock at the synthetic window
OFF = 2e9                     # trace clock minus perf_counter_ns


@pytest.fixture(scope="module")
def R():
    name = "bench_run_under_test"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(BENCH, "run.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


@pytest.fixture(scope="module")
def PS(R):
    return R.lib("program_spans")


class Rec(NamedTuple):
    name: str
    index: int
    parent: Optional[int]
    thread: int
    start_ns: float
    end_ns: float
    attrs: dict = {}
    error: bool = False


def _rec(name, index, parent, a, b, thread=1, **attrs):
    """A record at [a, b] ms of the trace's clock, on the program's."""
    return Rec(name, index, parent, thread, T0 + a * MS - OFF,
               T0 + b * MS - OFF, attrs)


def _records(b_end=79.9, setup=True):
    """A set-up variable, then the window's two variables A and B."""
    out = [_rec("repro.advise.variable", 0, None, -50, -20),
           _rec("repro.advise.train", 1, 0, -49, -30)] if setup else []
    return out + [
        _rec("repro.advise.variable", 10, None, 10, 39.9),
        _rec("repro.advise.train", 11, 10, 11, 25, compressor="sz2"),
        _rec("repro.train.sweep", 12, 11, 11, 13),
        _rec("repro.train.compress", 13, 11, 13, 24),
        _rec("repro.compress.run", 14, 13, 13, 24),
        _rec("repro.advise.stream", 15, 10, 26, 36),
        _rec("repro.stream.launch", 16, 15, 26, 27, rows=67,
             rows_launched=67),
        _rec("repro.stream.drain", 17, 15, 27, 35),
        _rec("repro.advise.recommend", 18, 10, 36, 39),
        _rec("repro.stream.read", 19, None, 25.5, 26, thread=2),
        _rec("repro.advise.variable", 20, None, 45.02, b_end),
        _rec("repro.advise.train", 21, 20, 46, 60, compressor="sz2"),
        _rec("repro.train.compress", 22, 21, 47, 59),
        _rec("repro.compress.run", 23, 22, 47, 59),
        _rec("repro.advise.stream", 24, 20, 61, 75),
        _rec("repro.stream.launch", 25, 24, 61, 62, rows=33,
             rows_launched=67),
        _rec("repro.stream.drain", 26, 24, 62, 74),
        _rec("repro.advise.recommend", 27, 20, 75, 79),
        _rec("repro.stream.read", 28, None, 90, 91, thread=2),
    ]


def _ctx(R, variables=((10, 40), (45, 80)), device=True):
    TR = R.lib("trace")

    def ev(plane, line, name, a, b):
        return TR.Event(plane, line, name, T0 + a * MS, (b - a) * MS)

    events = [ev(TR.HOST_PLANE, "python", "bench.window", 0, 100)]
    events += [ev(TR.HOST_PLANE, "python", "bench.variable", a, b)
               for a, b in variables]
    if device:
        events += [ev("/device:TPU:0", TR.OPS_LINE, "op", a, b)
                   for a, b in ((12, 20), (30, 35), (50, 70))]
    cell = R.load_cell("hurricane-advise")
    return R.Context(cell, 1, True, [], None, events=events,
                     planes=TR.devices(events),
                     window=TR.window_bounds(events))


def _read(R, ctx, name):
    return R.load_module(os.path.join(BENCH, "metrics",
                                      name + ".py")).read(ctx)


def test_window_spans_shift_and_choose_the_window_variables(R, PS):
    spans = PS.window_spans(_ctx(R), _records())
    by = {s.index: s for s in spans}
    assert set(by) == set(range(10, 28))      # no set-up, no late read
    assert by[10].start_ns == T0 + 10 * MS    # the first pair's offset
    assert by[14].end_ns == T0 + 24 * MS
    assert by[19].thread == 2 and by[19].variable == 0
    assert [by[i].variable for i in (10, 16, 20, 25)] == [0, 0, 1, 1]
    assert by[16].attrs == {"rows": 67, "rows_launched": 67}


@pytest.mark.parametrize("case", ["no variable", "too few records",
                                  "outside its bench span",
                                  "no program spans"])
def test_window_spans_refuse_what_does_not_match(R, PS, monkeypatch, case):
    ctx, records = _ctx(R), _records()
    if case == "no variable":
        ctx = _ctx(R, variables=())
    elif case == "too few records":
        ctx = _ctx(R, variables=((5, 8), (10, 40), (45, 80)))
        records = _records(setup=False)
    elif case == "outside its bench span":
        records = _records(b_end=81.5)
    else:
        monkeypatch.setitem(sys.modules, "repro.obs", None)
        records = None
    assert PS.window_spans(ctx, records) is None
    if case == "no program spans":
        for name in NEW:                       # as at a parent commit
            assert _read(R, ctx, name) is None


def test_readers_on_a_synthetic_trace(R, PS, monkeypatch):
    monkeypatch.setattr(PS, "program_records", _records)
    ctx = _ctx(R)
    assert _read(R, ctx, "advise_train_share") == pytest.approx(
        100 * 28 / (29.9 + 34.88))
    assert _read(R, ctx, "compress_run_ms") == pytest.approx(11.5)
    assert _read(R, ctx, "stream_pad_share") == pytest.approx(
        100 * 34 / 134)
    # idle [0,12] [20,30] [35,50] [70,100] against train [11,25] [46,60]
    assert _read(R, ctx, "idle_in_train.advise") == pytest.approx(10.0)
    assert _read(R, _ctx(R, device=False), "idle_in_train.advise") is None
    split = {k: v * 1e3 for k, v in PS.idle_by_span(     # s -> ms
        ctx, PS.window_spans(ctx), "/device:TPU:0").items()}
    assert split == pytest.approx({
        "repro.advise.variable": 1 + 1 + 0.9 + 0.98 + 0.9,
        "repro.train.sweep": 1, "repro.compress.run": 4 + 3,
        "repro.advise.train": 1 + 1, "repro.stream.launch": 1,
        "repro.stream.drain": 3 + 4, "repro.advise.stream": 1 + 1,
        "repro.advise.recommend": 3 + 4}, abs=1e-9)
    assert sum(split.values()) == pytest.approx(
        16.9 + 14.88)                          # idle inside the variables


def test_the_new_metrics_read_the_advisor_cell_only(R):
    names = [m["name"] for m in R.load_cell("hurricane-advise").per_layer()]
    assert names[-len(NEW):] == list(NEW)
    sweep = [m["name"] for m in R.load_cell("miranda-sweep").per_layer()]
    assert not set(NEW) & set(sweep)


def test_traced_rehearsal_reports_the_span_metrics(R, tmp_path):
    """``hurricane-advise`` traced on the CPU at a tiny size, with chunks
    of 4 rows of 6: 2 padded rows of every 8 launched."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        b = json.load(f)
    conf_path = "bench/configs/tiny_hurricane.json"
    for c in b["configs"]:
        if c["name"] == "hurricane":
            c["file"] = conf_path
    with open(os.path.join(BENCH, "configs", "hurricane.json")) as f:
        conf = dict(json.load(f), fields=["a", "b", "c"], slices=6, edge=20)
    with open(os.path.join(BENCH, "traffic", "advise.json")) as f:
        mix = dict(json.load(f), budget_mb=4 * 20 * 20 * 4 / 2 ** 20)
    for d in ("configs", "traffic"):
        os.makedirs(tmp_path / "bench" / d)
    (tmp_path / conf_path).write_text(json.dumps(conf))
    (tmp_path / "bench" / "traffic" / "advise.json").write_text(
        json.dumps(mix))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    line = R.run("hurricane-advise", 2 ** 33 + 7, 0.3, True,
                 root=str(tmp_path), rehearsal=True)
    assert line["correct"], line["checks"]
    m = line["metrics"]
    assert set(m) == set(NEW) - {"idle_in_train.advise"}   # no TPU plane
    assert m["stream_pad_share"] == {"value": 25.0, "unit": "%"}
    assert 0 < m["advise_train_share"]["value"] < 100
    assert m["compress_run_ms"]["value"] > 0
