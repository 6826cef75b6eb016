#!/usr/bin/env python3
"""Run one benchmark cell and print its result as one JSON line.

    python bench/run.py --workload miranda-sweep --seed 7 --seconds 10 --trace 0

Everything is found by name from ``BENCHMARK.json``: the cell names a
configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); the mix names the driver that runs
it (``bench/drivers/<driver>.py``); the cell's comparison limits are in
``bench/limits/<workload>.json``; each per-layer metric is read by
``bench/metrics/<metric>.py``.  A new cell, configuration, mix or
metric is a new file and a new ``BENCHMARK.json`` entry.

A run: set-up (device data from the seed, warm-up of the cell's own
shapes; ``setup_s`` counts from process start to the first timed
request), the measured window of ``--seconds``, then, with the
program's state freed, the comparison against the plain reference that
decides ``correct``.  ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics from a profiler trace of
the window (at most ``TRACE_WINDOW_S`` of it).  The compared numbers
and their limits are the last lines on standard error and the last key
of the result line.  Without a TPU, or with fewer chips than the cell
asks for, the run exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))   # the system under test
TRACE_WINDOW_S = 12.0     # a traced run measures at most this long
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def find(root: str, kind: str, name: str, ext: str) -> str:
    """``<root>/bench/<kind>/<name><ext>``, else the same file beside
    this harness."""
    for base in (os.path.join(root, "bench"), BENCH):
        path = os.path.join(base, kind, name + ext)
        if os.path.exists(path):
            return path
    raise FileNotFoundError(f"no {kind} file named {name + ext!r}")


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    name = "bench_" + os.path.relpath(path, os.path.dirname(os.path.dirname(
        path)))[:-3].replace(os.sep, "_").replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


_LIBS: Dict[str, Any] = {}


def lib(name: str):
    """A module of the harness itself (``"trace"``, ``"data"``,
    ``"ref/oracle"``), loaded once by path so that no name of it shadows
    another module."""
    if name not in _LIBS:
        _LIBS[name] = load_module(os.path.join(BENCH, name + ".py"))
    return _LIBS[name]


@dataclass
class Cell:
    """One ``workloads`` entry with everything it names."""
    root: str
    spec: dict
    bench: dict
    config: dict
    mix: dict
    limits: dict

    @property
    def name(self) -> str:
        return self.spec["name"]

    def end_to_end(self) -> List[dict]:
        return [m for m in self.bench["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    def per_layer(self) -> List[dict]:
        e2e = {m["name"] for m in self.end_to_end()}
        return [m for m in self.bench["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in e2e)]


def load_cell(workload: str, root: str = ROOT) -> Cell:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    spec = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if spec is None:
        raise SystemExit(f"unknown workload {workload!r}")
    conf = next(c for c in bench["configs"] if c["name"] == spec["config"])
    return Cell(root, spec, bench,
                config=load_json(os.path.join(root, conf["file"])),
                mix=load_json(find(root, "traffic", spec["traffic"], ".json")),
                limits=load_json(find(root, "limits", workload, ".json")))


@dataclass
class Context:
    """What a driver and a metric reader get."""
    cell: Cell
    seed: int
    trace: bool
    devices: list
    peaks: Optional[dict]
    clock: Any = None
    counters: Dict[str, Any] = field(default_factory=dict)
    events: list = field(default_factory=list)
    planes: List[str] = field(default_factory=list)
    window: tuple = (0.0, 0.0)

    @staticmethod
    def lib(name: str):
        return lib(name)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (a persistent
    cache hit counts as its retrieval) and how many backend compiles ran,
    from jax.monitoring."""

    def __init__(self, jax):
        self.s = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.s += duration
        if event == COMPILE_EVENTS[-1]:
            self.compiles += 1


def peaks_for(kind: str) -> dict:
    table = load_json(os.path.join(BENCH, "peaks.json"))["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"bench/peaks.json")
    return table[kind]


def chips(jax, n: int, require_tpu: bool) -> list:
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, found {devs[0].platform}")
    if len(devs) < n:
        raise NoChip(f"the cell asks for {n} chips, found {len(devs)}")
    return devs[:n]


def enable_compile_cache(jax) -> str:
    from repro.launch import compile_cache
    path = compile_cache.enable()
    # every program of the cell is cached, however fast it compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def read_trace(ctx: Context, tdir: str) -> None:
    import glob
    TR = lib("trace")
    paths = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace file, found {paths}")
    ctx.events = TR.load_events(paths[0])
    ctx.planes = [p for p in TR.devices(ctx.events)][:len(ctx.devices)]
    ctx.window = TR.window_bounds(ctx.events)


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        root: str = ROOT, rehearsal: bool = False) -> dict:
    """One run of one cell; returns the result line's object.

    ``rehearsal`` (the tests' CPU runs) skips the look for a TPU and
    leaves JAX's persistent compilation cache off."""
    cell = load_cell(workload, root)
    import jax
    require_tpu = not rehearsal
    devs = chips(jax, int(cell.spec["chips"]), require_tpu)
    cache = "off" if rehearsal else enable_compile_cache(jax)
    peaks = peaks_for(devs[0].device_kind) if require_tpu else None
    ctx = Context(cell, int(seed), bool(trace), devs, peaks,
                  clock=CompileClock(jax))
    drv = load_module(find(root, "drivers", cell.mix["driver"], ".py"))
    log(f"bench: {workload} seed {seed} on {len(devs)} x "
        f"{devs[0].device_kind}, compile cache {cache}")

    state = drv.setup(ctx)
    setup_s = time.perf_counter() - T_START
    compile_s, compiles0 = ctx.clock.s, ctx.clock.compiles
    log(f"bench: set-up {setup_s:.3f} s, of it compile {compile_s:.3f} s")

    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
    # the profiler's cost grows with the events it records: a traced run
    # reads a shorter steady window, its per-layer metrics need no more
    window_s = min(float(seconds), TRACE_WINDOW_S) if trace else float(seconds)
    with jax.profiler.TraceAnnotation("bench.window"):
        res = drv.window(state, window_s, ctx)
    window_compiles = ctx.clock.compiles - compiles0
    if trace:
        if hasattr(drv, "probes"):
            drv.probes(state, ctx)
        jax.profiler.stop_trace()
    memory_peak = max(int(d.memory_stats()["peak_bytes_in_use"])
                      for d in devs) if require_tpu else 0
    ctx.counters.update(res.get("counters", {}))
    kept = drv.release(state, ctx)
    del state
    gc.collect()
    log(f"bench: window compiles {window_compiles}")

    line: Dict[str, Any] = {"correct": False,
                            "attempted": int(res["attempted"]),
                            "failed": int(res["failed"]),
                            "metrics": {}}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    if trace:
        TR = lib("trace")
        try:
            read_trace(ctx, tdir)
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        lo, hi = ctx.window
        device.update(TR.device_summary(ctx.events, ctx.planes, lo, hi))
        for m in cell.per_layer():
            reader = load_module(find(root, "metrics", m["name"], ".py"))
            value = reader.read(ctx)
            if value is not None:
                line["metrics"][m["name"]] = {"value": float(value),
                                              "unit": m["unit"]}
        line["breakdown"] = TR.breakdown(ctx.events, ctx.planes, lo, hi)
    else:
        values = dict(res["metrics"], setup_s=setup_s)
        for m in cell.end_to_end():
            line["metrics"][m["name"]] = {"value": float(values[m["name"]]),
                                          "unit": m["unit"]}
    line["device"] = device

    numbers = drv.check(kept, ctx)
    checks = {k: {"value": float(numbers[k]), "limit": float(lim)}
              for k, lim in cell.limits["limits"].items()}
    line["correct"] = all(c["value"] <= c["limit"] for c in checks.values())
    line["checks"] = checks
    for k, c in checks.items():
        log(f"check {k} {c['value']!r} limit {c['limit']!r}")
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        line = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        log(f"bench: {e}")
        return 2
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
