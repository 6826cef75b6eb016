"""CPU tests of the chip benchmark's harness (``bench/``).

They cover the trace reduction on a recorded trace, the work counts of
the roofline readers, the contract of ``BENCHMARK.json``, the copied
references against ``chip_smoke.py``'s and the compressors' own byte
counts, discovery of cells by file name, and that the comparison
deciding ``correct`` rejects the lower-precision control, answers
handed to the wrong row, and a broken timed path.  Runs of the harness
here are rehearsals: tiny shapes, no TPU, no compile cache.
"""
from __future__ import annotations

import copy
import importlib.util
import json
import os
import re
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
BENCH = os.path.join(REPO, "bench")


def _load(path: str, name: str):
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def R():
    return _load(os.path.join(BENCH, "run.py"), "bench_run_under_test")


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# trace reduction
# ---------------------------------------------------------------------------

def _ev(R, plane, line, name, start, dur):
    return R.lib("trace").Event(plane, line, name, float(start), float(dur))


def test_busy_union_idle_share_and_gaps(R):
    TR = R.lib("trace")
    d0, d1 = "/device:TPU:0", "/device:TPU:1"
    ev = [_ev(R, TR.HOST_PLANE, "python", "bench.window", 0, 1000),
          _ev(R, TR.HOST_PLANE, "python", "bench.sync", 300, 400),
          # overlapping ops count once; the part outside the window not at all
          _ev(R, d0, TR.OPS_LINE, "sort", -50, 150),
          _ev(R, d0, TR.OPS_LINE, "fusion", 50, 150),
          _ev(R, d0, TR.OPS_LINE, "sort", 700, 200),
          _ev(R, d0, TR.MODULES_LINE, "jit_f(1)", 0, 200),
          _ev(R, d0, TR.MODULES_LINE, "jit_f(1)", 700, 200),
          _ev(R, d1, TR.OPS_LINE, "sort", 0, 500)]
    lo, hi = TR.window_bounds(ev)
    assert (lo, hi) == (0.0, 1000.0)
    assert TR.busy_intervals(ev, d0, lo, hi) == [(0.0, 200.0), (700.0, 900.0)]
    assert TR.idle_share(ev, [d0], lo, hi) == pytest.approx(60.0)
    assert TR.idle_share(ev, [d0, d1], lo, hi) == pytest.approx(55.0)
    assert TR.device_summary(ev, [d0], lo, hi) == pytest.approx(
        {"busy_s": 4e-7, "window_s": 1e-6})
    gaps = TR.idle_gaps(ev, d0, lo, hi)
    assert gaps[0] == ["bench.sync", pytest.approx(5e-7)]
    assert gaps[1] == ["no benchmark span", pytest.approx(1e-7)]
    assert TR.mean_launch_ms(ev, [d0, d1], r"jit_f") == pytest.approx(2e-4)
    assert TR.mean_launch_ms(ev, [d0], r"jit_g") is None
    ops = dict(TR.top_ops(ev, [d0], lo, hi))
    assert ops == {"sort": pytest.approx(2e-7), "fusion": pytest.approx(1.5e-7)}


def test_recorded_trace_reduces(R):
    """A trace recorded on a TPU v5e: two sweep launches of a (2, 128,
    128) stack inside a window span.  The device's clock runs about a
    millisecond from the host's, so the first launch may seem to start
    before the span that issued it."""
    TR = R.lib("trace")
    with open(os.path.join(BENCH, "testdata", "trace_events.json")) as f:
        ev = [TR.Event(*e) for e in json.load(f)]
    planes = TR.devices(ev)
    assert planes and planes[0] == "/device:TPU:0"
    lo, hi = TR.window_bounds(ev)
    s = TR.device_summary(ev, planes[:1], lo, hi)
    assert 0 < s["busy_s"] < s["window_s"]
    idle = TR.idle_share(ev, planes[:1], lo, hi)
    assert 0 < idle < 100
    launches = TR.module_launches(ev, planes[0], r"features_sweep")
    assert len(launches) == 2
    ms = TR.mean_launch_ms(ev, planes[:1], r"features_sweep")
    assert ms == pytest.approx(
        sum(e.dur_ns for e in launches) / 2 / 1e6)
    b = TR.breakdown(ev, planes[:1], lo, hi)
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert sum(v for _, v in b["device_ops"]) <= s["busy_s"] * (1 + 1e-9)
    assert all(name.startswith("bench.") or name == "no benchmark span"
               for name, _ in b["idle_gaps"])


# ---------------------------------------------------------------------------
# work counts and peaks
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("config,k,edge", [("miranda", 256, 384),
                                           ("hurricane", 100, 500)])
def test_work_counts_by_hand(R, config, k, edge):
    with open(os.path.join(BENCH, "configs", config + ".json")) as f:
        c = json.load(f)
    assert (c["slices"], c["edge"]) == (k, edge)
    trunc = R.load_module(os.path.join(BENCH, "metrics", "trunc_roofline.py"))
    qent = R.load_module(os.path.join(BENCH, "metrics", "qent_roofline.py"))
    flops, nbytes = trunc.work(k, edge, edge)
    # one Gram of an edge x edge slice: edge^2 dot products of length edge
    assert flops == 2 * k * edge ** 3
    assert nbytes == k * edge * edge * 4
    assert qent.work(k, edge, edge) == (0.0, k * edge * edge * 4)
    assert trunc.work(1, 3, 5) == (2 * 5 * 3 * 3, 60)
    if config == "miranda":
        assert flops == 28991029248 and nbytes == 150994944


def test_roofline_share_and_peaks(R):
    TR = R.lib("trace")
    peaks = R.peaks_for("TPU v5 lite")
    assert peaks == {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                     "hbm_bytes": 16e9}
    share, bound = TR.roofline_share(0.0, 819e9, 2.0, peaks)
    assert (share, bound) == (pytest.approx(50.0), "memory")
    share, bound = TR.roofline_share(197e12, 1.0, 4.0, peaks)
    assert (share, bound) == (pytest.approx(25.0), "compute")
    with pytest.raises(KeyError):
        R.peaks_for("cpu")


def test_no_chip_exits_without_result(R, capsys):
    assert R.main(["--workload", "miranda-sweep", "--seed", "1",
                   "--seconds", "1"]) == 2
    assert capsys.readouterr().out == ""


# ---------------------------------------------------------------------------
# BENCHMARK.json
# ---------------------------------------------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_benchmark_names_units_and_shape(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["command"] == ["python3", "bench/run.py"]
    assert 1 <= spec["run_seconds"] <= 51
    metrics = spec["end_to_end"] + spec["per_layer"]
    names = [m["name"] for m in metrics] + [w["name"] for w in
                                            spec["workloads"]]
    names += [c["name"] for c in spec["configs"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for w in spec["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert all(NAME.match(k) for k in c["reduced"])
        assert c["file"].startswith(tuple(p + "/" for p in spec["paths"]))
    for m in metrics:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for m in spec["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25
    for m in spec["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert "\n" not in m["layer"] and "bound" not in m


def test_every_cell_is_complete(R, spec):
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    for w in spec["workloads"]:
        cell = R.load_cell(w["name"])
        reported = {m["name"] for m in cell.end_to_end()}
        assert "setup_s" in reported and len(reported) >= 2, w["name"]
        layer = cell.per_layer()
        assert layer, w["name"]
        for m in layer:
            assert m["moves"] in reported, (w["name"], m["name"])
            assert os.path.exists(os.path.join(BENCH, "metrics",
                                               m["name"] + ".py"))
        assert os.path.exists(R.find(REPO, "drivers", cell.mix["driver"],
                                     ".py"))
        assert cell.limits["limits"]
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        for wl in m.get("workloads", []):
            assert wl in e2e[m["moves"]].get("workloads", [wl])


# ---------------------------------------------------------------------------
# the reference
# ---------------------------------------------------------------------------

def test_oracle_agrees_with_chip_smoke(R):
    O = R.lib("ref/oracle")
    smoke = _load(os.path.join(REPO, "chip_smoke.py"), "chip_smoke_for_bench")
    rng = np.random.default_rng(0)
    x = (rng.standard_normal((24, 40)) * 3).astype(np.float32)
    flat = x.reshape(-1)
    for eps in (np.float32(1e-3), np.float32(0.05), np.float32(0.7)):
        assert np.array_equal(O.codes_f32(flat, eps), smoke.codes_f32(flat, eps))
        assert O.entropy_f64(O.codes_f32(flat, eps)) == smoke.entropy_f64(
            smoke.codes_f32(flat, eps))
        p, n = O.quality_f64(flat, eps)
        ps, ns = smoke.quality_f64(flat, eps, 2048)[:2]
        assert p == pytest.approx(ps, rel=1e-12)
        assert n == pytest.approx(ns, rel=1e-12)
    assert O.truncation_f64(x, 0.99) == smoke.truncation_f64(x, 0.99)


def test_reference_row_round_trips(R):
    O = R.lib("ref/oracle")
    rng = np.random.default_rng(1)
    x = rng.standard_normal((16, 16)).astype(np.float32)
    ebs = np.asarray([1e-3, 1e-2, 1e-1], np.float32)
    ref = O.row_reference(x, ebs, 0.99)
    d = O.deviations(O.reference_row(ref), ref)
    assert all(v < 1e-9 for v in d.values())
    assert set(O.deviations(O.reference_row(ref)[:, :2], ref)) == {
        "qent_bits", "trunc_mass"}


def test_truncation_is_judged_by_the_mass_it_misses(R):
    """A count one off the reference's where the mass sits within float32
    rounding of the variance fraction misses it by that rounding only;
    elsewhere by a share of the mass that the limit rejects."""
    O = R.lib("ref/oracle")
    cum = np.array([0.5, 0.9, 0.99 - 3e-7, 0.995, 1.0])
    assert O.mass_missed(cum, 4, 0.99) == 0.0
    assert O.mass_missed(cum, 3, 0.99) == pytest.approx(3e-7, rel=1e-6)
    assert O.mass_missed(cum, 5, 0.99) == pytest.approx(5e-3)
    assert O.mass_missed(cum, 2, 0.99) == pytest.approx(0.09)
    assert O.mass_missed(cum, 0, 0.99) == pytest.approx(0.99)
    assert O.mass_missed(cum, 9, 0.99) == pytest.approx(0.01)


@pytest.mark.parametrize("name", ["sz2", "sz3-lorenzo", "sz3-regression",
                                  "sz3-interp", "zfp", "mgard",
                                  "bitgrooming", "digitrounding"])
def test_reference_sizes_agree_with_the_compressors(R, name):
    """The reference's byte count of a run's codes is the compressor's
    own, and its error-bound ratio is at most 1 for a sound run and over
    1 for codes moved by one step."""
    from repro import compressors as C
    S = R.lib("ref/sizes")
    comp = C.get(name)
    rng = np.random.default_rng(3)
    x = np.cumsum(rng.standard_normal((24, 40)), axis=1).astype(np.float32)
    for rel in (1e-4, 1e-3, 1e-2):
        eps = rel * float(x.max() - x.min())
        codes, aux = comp.encode(x, eps)
        assert S.size_bytes(name, codes, aux, eps) == comp.size_bytes(
            codes, aux, eps), (name, rel)
        recon = np.asarray(comp.decode(codes, aux, eps))
        assert S.bound_ratio(x, recon, eps) <= 1.0, (name, rel)
        assert S.bound_ratio(x, recon + np.float32(3 * eps), eps) > 1.0


# ---------------------------------------------------------------------------
# runs of the harness at tiny size
# ---------------------------------------------------------------------------

TINY = {"miranda": {"fields": ["a", "b"], "slices": 8, "edge": 32},
        "hurricane": {"fields": ["a", "b", "c"], "slices": 6, "edge": 20}}
@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory, spec):
    """A checkout of throwaway files: tiny copies of the configurations,
    found by their names."""
    root = tmp_path_factory.mktemp("tiny")
    b = copy.deepcopy(spec)
    for c in b["configs"]:
        c["file"] = f"bench/configs/tiny_{c['name']}.json"
        with open(os.path.join(REPO, "bench", "configs",
                               c["name"] + ".json")) as f:
            conf = dict(json.load(f), **TINY[c["name"]])
        os.makedirs(root / "bench" / "configs", exist_ok=True)
        (root / c["file"]).write_text(json.dumps(conf))
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    return str(root)


def test_every_seed_sweeps_the_same_slices_in_another_order(R):
    """The sweep's work is the same for every seed: the fields come from
    a fixed seed, and the run's seed only orders each stack's slices."""
    D = R.lib("data")
    made = [D.make_fields(["a", "b"], 1, generator="miranda_like", count=6,
                          n=16) for _ in range(3)]
    orders = [D.shuffle_slices(m[0], s) for m, s in
              zip(made, (5, 2 ** 40 + 5, 5))]
    a, b, again = ([np.asarray(x) for x in o] for o in orders)
    for x, y, z in zip(a, b, again):
        assert np.array_equal(x, z)
        assert not np.array_equal(x, y)
        assert np.array_equal(np.sort(x.reshape(6, -1), 0),
                              np.sort(y.reshape(6, -1), 0))


def test_a_new_config_mix_and_metric_are_found_by_name(R, spec, tmp_path):
    """A cell added as files only: its configuration, its mix, its limits
    and a per-layer metric reader, none of them known to the harness."""
    b = copy.deepcopy(spec)
    b["configs"].append({"name": "toy", "source": "https://example.org/toy",
                         "file": "bench/configs/toy.json", "reduced": [],
                         "why": "a throwaway"})
    b["workloads"].append({"name": "toy-loop", "config": "toy",
                           "traffic": "toy_mix", "chips": 1,
                           "why": "a throwaway"})
    b["end_to_end"][0].setdefault("workloads", []).append("toy-loop")
    b["per_layer"].append({"name": "toy_launches", "unit": "launches",
                           "better": "higher", "source": "program_counter",
                           "layer": "sweep engine", "moves": "sweep_rate",
                           "workloads": ["toy-loop"]})
    for d in ("configs", "traffic", "limits", "metrics"):
        os.makedirs(tmp_path / "bench" / d)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    conf = dict(TINY["miranda"], fields=["t"], generator="miranda_like",
                eps=1e-4, n_ebs=2, eb_top=1e-2, variance_fraction=0.99)
    (tmp_path / "bench" / "configs" / "toy.json").write_text(json.dumps(conf))
    (tmp_path / "bench" / "traffic" / "toy_mix.json").write_text(json.dumps(
        {"driver": "sweep", "data_seed": 1, "check_rows": 2,
         "sweep_module": "features_sweep"}))
    (tmp_path / "bench" / "limits" / "toy-loop.json").write_text(json.dumps(
        {"limits": {"qent_bits": 1e-3, "trunc_mass": 1e-4}}))
    (tmp_path / "bench" / "metrics" / "toy_launches.py").write_text(
        "def read(ctx):\n    return ctx.counters['launches']\n")
    cell = R.load_cell("toy-loop", str(tmp_path))
    assert cell.config["fields"] == ["t"] and cell.mix["check_rows"] == 2
    assert [m["name"] for m in cell.per_layer()] == ["toy_launches"]
    line = R.run("toy-loop", 5, 0.3, True, root=str(tmp_path),
                 rehearsal=True)
    assert line["correct"], line["checks"]
    assert line["metrics"]["toy_launches"]["value"] == line["attempted"] > 0
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("workload", ["miranda-sweep", "hurricane-advise"])
def test_tiny_run_is_correct(R, tiny_root, workload):
    line = R.run(workload, 2 ** 32 + 3, 0.5, False, root=tiny_root,
                 rehearsal=True)
    assert line["correct"], line["checks"]
    cell = R.load_cell(workload, tiny_root)
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end()}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["attempted"] > 0 and line["failed"] == 0


@pytest.mark.parametrize("workload", ["miranda-sweep", "hurricane-advise"])
def test_control_is_rejected(R, tiny_root, workload):
    """The reference one precision step down (bfloat16 data and
    quotients) in the program's place fails the cell's own limits."""
    cell = R.load_cell(workload, tiny_root)
    drv = R.load_module(R.find(tiny_root, "drivers", cell.mix["driver"],
                               ".py"))
    import jax
    ctx = R.Context(cell, 9, False, jax.devices()[:1], None,
                    clock=R.CompileClock(jax))
    st = drv.setup(ctx)
    drv.window(st, 1.0, ctx)
    kept = drv.release(st, ctx)
    sound, ctrl = drv.check(kept, ctx), drv.control(kept, ctx)
    limits = cell.limits["limits"]
    assert all(sound[k] <= limits[k] for k in limits)
    assert any(ctrl[k] > limits[k] for k in ctrl), ctrl


def _alter_sweep(monkeypatch, how):
    from repro.core import predictors as P
    real = P.features_sweep

    def broken(slices, epss, *a, **kw):
        if how == "half":                 # rows past the middle left out
            k = slices.shape[0]
            slices = slices.at[k // 2:].set(slices[: k - k // 2])
        out = real(slices, epss, *a, **kw)
        if how == "answer":               # every q-ent off by 1% relative
            f, q = out
            return f.at[..., 0].add(0.01), q
        return out

    monkeypatch.setattr(P, "features_sweep", broken)


@pytest.mark.parametrize("how", ["answer", "half"])
def test_broken_sweep_is_not_correct(R, tiny_root, monkeypatch, how):
    _alter_sweep(monkeypatch, how)
    line = R.run("miranda-sweep", 17, 0.3, False, root=tiny_root,
                 rehearsal=True)
    assert not line["correct"], line["checks"]


def test_broken_stream_is_not_correct(R, tiny_root, monkeypatch):
    from repro.core import stream as ST
    real = ST.stream_features

    def broken(*a, **kw):
        f, q = real(*a, **kw)
        return np.asarray(f) + np.float32(0.01), q

    monkeypatch.setattr(ST, "stream_features", broken)
    line = R.run("hurricane-advise", 18, 0.3, False, root=tiny_root,
                 rehearsal=True)
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("how", ["ratio", "bound", "unrun"])
def test_broken_training_run_is_not_correct(R, tiny_root, monkeypatch, how):
    """A training ratio altered where the table is made, Lorenzo codes
    moved by one step where they are made, or a table made without
    running the compressors."""
    from repro.dist import sweep as DS
    real_table = DS.training_crs
    number = "cr_rel"
    if how == "ratio":
        monkeypatch.setattr(DS, "training_crs",
                            lambda *a, **kw: real_table(*a, **kw) * 1.05)
    elif how == "unrun":
        monkeypatch.setattr(DS, "training_crs", lambda comp, slices, ebs,
                            **kw: np.full((len(slices), len(ebs)), 4.0))
    else:
        from repro.compressors import sz
        real = sz.lorenzo_encode
        monkeypatch.setattr(sz, "lorenzo_encode",
                            lambda d, e: real(d, e).at[0, 0].add(1))
        number = "bound_ratio"
    line = R.run("hurricane-advise", 20, 0.3, False, root=tiny_root,
                 rehearsal=True)
    assert not line["correct"], line["checks"]
    bad = line["checks"][number]
    assert bad["value"] > bad["limit"]


@pytest.mark.parametrize("workload", ["miranda-sweep", "hurricane-advise"])
def test_answers_of_other_rows_are_rejected(R, tiny_root, workload):
    """The calibration's fault, every answer handed to its neighbour,
    fails the truncation's limit and every other."""
    cell = R.load_cell(workload, tiny_root)
    drv = R.load_module(R.find(tiny_root, "drivers", cell.mix["driver"],
                               ".py"))
    import jax
    ctx = R.Context(cell, 21, False, jax.devices()[:1], None,
                    clock=R.CompileClock(jax))
    st = drv.setup(ctx)
    drv.window(st, 1.0, ctx)
    kept = drv.release(st, ctx)
    bad = drv.fault(kept, ctx)
    limits = cell.limits["limits"]
    assert bad["trunc_mass"] > limits["trunc_mass"], bad
    assert bad["qent_bits"] > limits["qent_bits"], bad
