"""The advisor's span tree (``repro.obs`` spans in ``launch/advise.py``,
``core/usecases.py``, ``compressors/base.py`` and ``core/stream.py``):
one tree per variable, as many compressor runs as the training table
has cells, launches that cover the variable's rows, and a report that
does not depend on the spans being recorded."""
import pytest

from repro import obs
from repro.core import stream as ST
from repro.data import source as SRC
from repro.launch import advise as ADV

COMPRESSORS = ("sz3-lorenzo", "bitgrooming")
GRID = (1e-3, 1e-2)
TRAIN_ROWS = 3
ROWS, EDGE, CHUNK = 6, 24, 4


@pytest.fixture(scope="module")
def dataset(tmp_path_factory):
    gen = SRC.GeneratorSource([
        SRC.FieldVariable("miranda-vx", ROWS, (EDGE,), seed=1),
        SRC.FieldVariable("cesm-cloud", ROWS, (EDGE,), seed=2)])
    path = SRC.write_dataset(str(tmp_path_factory.mktemp("ds") / "ds"), gen,
                             fmt="memmap", dtype="float32")
    return SRC.open_dataset(path)


def _advise(source, prefetch=2, service=None):
    return ADV.advise_dataset(
        source, compressors=COMPRESSORS, grid_rels=GRID, targets=(4.0, 8.0),
        train_rows=TRAIN_ROWS, psnr_floor=60.0, service=service,
        stream=ST.StreamConfig(budget_bytes=CHUNK * EDGE * EDGE * 4,
                               prefetch=prefetch))


def _traced(source, **kw):
    with obs.span("test.mark") as mark:
        pass
    report = _advise(source, **kw)
    return report, sorted((r for r in obs.records() if r.index > mark.index),
                          key=lambda r: r.index)


def _under(spans, root):
    """``root`` and every span below it, in the order they started."""
    kids = {}
    for s in spans:
        kids.setdefault(s.parent, []).append(s)
    out, todo = [], [root]
    while todo:
        s = todo.pop()
        out.append(s)
        todo += kids.get(s.index, [])
    return sorted(out, key=lambda s: s.index)


def _names(spans, name):
    return [s for s in spans if s.name == name]


@pytest.mark.parametrize("prefetch", [2, 0])
def test_one_span_tree_per_variable(dataset, prefetch):
    report, spans = _traced(dataset, prefetch=prefetch)
    variables = _names(spans, "repro.advise.variable")
    assert [v.attrs["variable"] for v in variables] == list(
        dataset.variables())
    for v in variables:
        meta = dataset.meta(v.attrs["variable"])
        assert v.parent is None and not v.error
        assert v.attrs["rows"] == ROWS
        assert v.attrs["nbytes"] == meta.nbytes_f32
        tree = _under(spans, v)
        kids = [s.name for s in tree if s.parent == v.index]
        assert kids == (["repro.advise.train"] * len(COMPRESSORS)
                        + ["repro.advise.stream", "repro.advise.recommend"])
        trains = _names(tree, "repro.advise.train")
        assert [t.attrs["compressor"] for t in trains] == list(COMPRESSORS)
        for t in trains:
            steps = [s for s in tree if s.parent == t.index]
            assert [s.name for s in steps] == [
                "repro.train.sweep", "repro.train.compress",
                "repro.train.fit"]
            assert steps[0].attrs == {"rows": TRAIN_ROWS, "ebs": len(GRID)}
            assert steps[1].attrs == {"runs": TRAIN_ROWS * len(GRID)}
            runs = [s for s in tree if s.parent == steps[1].index]
            assert len(runs) == TRAIN_ROWS * len(GRID)
            for r in runs:
                assert r.name == "repro.compress.run"
                assert r.attrs["compressor"] == t.attrs["compressor"]
                assert r.attrs["bytes"] > 0
                assert [s.name for s in tree if s.parent == r.index] == [
                    "repro.compress.encode", "repro.compress.size"]
        assert len(_names(tree, "repro.compress.run")) == (
            len(COMPRESSORS) * TRAIN_ROWS * len(GRID))
        launches = _names(tree, "repro.stream.launch")
        assert sum(s.attrs["rows"] for s in launches) == ROWS
        assert [s.attrs["rows_launched"] for s in launches] == [CHUNK] * 2
        assert len(_names(tree, "repro.stream.drain")) == len(launches)
        stream = _names(tree, "repro.advise.stream")[0]
        reads = [s for s in spans if s.name == "repro.stream.read"
                 and stream.start_ns <= s.start_ns <= s.end_ns
                 <= stream.end_ns]
        assert sum(s.attrs["rows"] for s in reads) == ROWS
        assert sum(s.attrs["bytes"] for s in reads) == meta.nbytes_f32
        if prefetch:        # the reader thread's roots
            assert all(s.parent is None and s.thread != v.thread
                       for s in reads)
            assert _names(tree, "repro.stream.wait")
        else:               # staged inline, on the advisor's thread
            assert all(s.parent == stream.index for s in reads)
            assert not _names(tree, "repro.stream.wait")
    assert all(not s.error for s in spans)


def test_the_service_route_has_the_advisor_spans(dataset):
    from repro.serve.sweep_service import ServiceConfig, SweepService
    svc = SweepService(ServiceConfig(max_wait_ms=1.0))
    try:
        report, spans = _traced(dataset, service=svc)
    finally:
        svc.close()
    for v in _names(spans, "repro.advise.variable"):
        kids = [s.name for s in spans if s.parent == v.index]
        assert kids[-2:] == ["repro.advise.stream", "repro.advise.recommend"]
    assert not _names(spans, "repro.stream.launch")
    assert set(report["variables"]) == set(dataset.variables())


def test_the_report_does_not_depend_on_the_spans(dataset, monkeypatch):
    traced = _advise(dataset)

    class Off:
        def __init__(self, name, **attrs):
            self.attrs = attrs

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(obs, "span", Off)
    before = obs.records()[-1].index
    assert _advise(dataset) == traced
    assert obs.records()[-1].index == before


def test_a_second_variable_of_a_shape_compiles_nothing(tmp_path):
    """The compressors' programs are traced while the first variable of
    a new row shape trains, and never for the second."""
    edge = 23               # a row shape no other test of this file uses
    gen = SRC.GeneratorSource([
        SRC.FieldVariable("scale-u", ROWS, (edge,), seed=3),
        SRC.FieldVariable("hurricane-u", ROWS, (edge,), seed=4)])
    source = SRC.open_dataset(SRC.write_dataset(
        str(tmp_path / "ds"), gen, fmt="memmap", dtype="float32"))
    _, spans = _traced(source)
    first, second = _names(spans, "repro.advise.variable")
    traces = _names(_under(spans, first), "repro.compress.trace")
    assert {s.attrs["compressor"] for s in traces} == set(COMPRESSORS)
    assert all(s.attrs["shape"] == (edge, edge) for s in traces)
    assert not _names(_under(spans, second), "repro.compress.trace")
