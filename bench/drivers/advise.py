"""The compression advisor as a user runs it, variable after variable.

Set-up makes the configuration's fields on the device, writes them as
a memmap dataset (the format of ``tools/make_dataset.py``, through the
program's ``write_dataset``) under the run's temporary directory, and
runs the advisor once over the first variable, which compiles and warms
every program the advisor uses.  The window then runs
``advise_dataset`` with the mix's settings on one variable after
another, from the second on, back to back; it ends at the first
completion at or after ``--seconds``.

Two things the advisor makes on its way to a recommendation are
recorded for the comparison, without changing what runs:

* the streamed (features, quality) tensor of each variable (the
  advisor's call into ``core.stream.stream_features`` is wrapped);
* for each variable, compressor and grid error bound, one training
  compressor run on a training row drawn from the seed: the codes its
  ``encode`` returned and the compression ratio that
  ``dist.sweep.training_crs`` put in the table the models train on
  (both calls are wrapped and passed through).

Mix keys: see ``bench/traffic/advise.json``.
"""
from __future__ import annotations

import os
import shutil
import tempfile
import time

import numpy as np


def _host_source(names, arrays):
    from repro.data import source as SRC

    class HostSource(SRC.DatasetSource):
        def variables(self):
            return tuple(names)

        def meta(self, name):
            return SRC.VariableMeta(name, tuple(arrays[name].shape),
                                    "float32")

        def read_rows(self, name, lo, hi):
            return np.ascontiguousarray(arrays[name][lo:hi])

    return HostSource()


def setup(ctx) -> dict:
    from repro.core import stream as ST
    from repro.data import source as SRC
    D = ctx.lib("data")
    c, mix = ctx.cell.config, ctx.cell.mix
    stacks, _, _ = D.make_fields(c["fields"], ctx.seed,
                                 generator=c["generator"],
                                 count=c["slices"], n=c["edge"])
    arrays = {f: np.asarray(s) for f, s in zip(c["fields"], stacks)}
    del stacks
    tmp = tempfile.mkdtemp(prefix="bench-advise-")
    path = SRC.write_dataset(os.path.join(tmp, "ds"),
                             _host_source(c["fields"], arrays),
                             fmt="memmap", dtype="float32")
    del arrays
    st = {"tmp": tmp, "source": SRC.open_dataset(path), "streamed": [],
          "runs": [], "picks": None, "expected": 0,
          "stream_features": ST.stream_features}

    def recording(*args, **kw):
        out = st["stream_features"](*args, **kw)
        st["streamed"].append((args[1], args[2], out))
        return out

    ST.stream_features = recording
    _record_training(st, mix)
    _advise(st, mix, c["fields"][0])
    st["streamed"].clear()
    return st


def _record_training(st: dict, mix: dict) -> None:
    """Wrap the program's ``training_crs`` and each mix compressor's
    ``encode``, passing every call through.  While ``st["picks"]`` maps
    (compressor, grid eb) to a training row, the call on that row at
    that eb keeps its input, its codes and the ratio the table holds."""
    from repro import compressors as C
    from repro.dist import sweep as DS
    st["restore"] = [(DS, "training_crs", DS.training_crs)]
    real_table = DS.training_crs

    def table(comp, slices, ebs, **kw):
        if st["picks"] is None:
            return real_table(comp, slices, ebs, **kw)
        st["current"] = (comp.name, slices, np.asarray(ebs, np.float64), [])
        try:
            out = real_table(comp, slices, ebs, **kw)
        finally:
            _, _, _, made = st.pop("current")
        for rec, r, j in made:
            st["runs"].append(rec + (float(np.asarray(out)[r, j]),))
        return out

    DS.training_crs = table
    for name in mix["compressors"]:
        comp = C.get(name)
        st["restore"].append((comp, "encode", None))

        def encode(data, eps, _real=comp.encode, _name=name):
            codes, aux = _real(data, eps)
            cur = st.get("current")
            if cur is not None and cur[0] == _name:
                _, slices, ebs, made = cur
                j = int(np.argmin(np.abs(ebs - float(eps))))
                r = st["picks"][_name][j]
                if (ebs[j] == float(eps) and r < len(slices)
                        and all(m[2] != j for m in made)
                        and np.array_equal(np.asarray(data), slices[r])):
                    made.append(((_name, np.array(data, np.float32),
                                  float(eps), codes, aux), r, j))
            return codes, aux

        comp.encode = encode


def _advise(st: dict, mix: dict, name: str) -> dict:
    from repro.core import stream as ST
    from repro.launch import advise as ADV
    return ADV.advise_dataset(
        st["source"], compressors=mix["compressors"] or None,
        grid_rels=tuple(mix["grid_rels"]), targets=tuple(mix["targets"]),
        train_rows=int(mix["train_rows"]),
        stream=ST.StreamConfig(budget_bytes=int(mix["budget_mb"] * 2**20),
                               prefetch=int(mix["prefetch"])),
        fields=[name], psnr_floor=mix["psnr_floor"])["variables"][name]


def window(st: dict, seconds: float, ctx) -> dict:
    import jax
    names = st["source"].variables()
    done, nbytes = [], 0
    t0 = time.perf_counter()
    t = t0
    mix = ctx.cell.mix
    rows, n_ebs = int(mix["train_rows"]), len(mix["grid_rels"])
    while t - t0 < seconds:
        name = names[(1 + len(done)) % len(names)]
        rng = np.random.default_rng([ctx.seed % (1 << 64), 5, len(done)])
        st["picks"] = {c: rng.integers(rows, size=n_ebs).tolist()
                       for c in mix["compressors"]}
        st["expected"] += len(mix["compressors"]) * n_ebs
        with jax.profiler.TraceAnnotation("bench.variable"):
            entry = _advise(st, ctx.cell.mix, name)
        done.append((name, entry))
        nbytes += st["source"].meta(name).nbytes_f32
        t = time.perf_counter()
    st["picks"] = None
    st["done"] = done
    return {"attempted": len(done), "failed": 0,
            "metrics": {"advise_rate": nbytes / 1e6 / (t - t0)},
            "counters": {"variables": len(done)}}


def release(st: dict, ctx) -> dict:
    """What the comparison needs, on the host where it is large: rows of
    the streamed tensors drawn from the seed, with their data rows and
    error bounds, and the recorded training runs, each with the ratio
    its table holds.  Then the dataset is deleted and the wrapped calls
    restored."""
    from repro.core import stream as ST
    ST.stream_features = st["stream_features"]
    for obj, attr, real in st["restore"]:
        if real is None:
            delattr(obj, attr)            # the instance's own wrapper
        else:
            setattr(obj, attr, real)
    rng = np.random.default_rng([ctx.seed % (1 << 64), 4])
    streamed = st["streamed"]
    rows = []
    for _ in range(int(ctx.cell.mix["check_rows"])):
        name, ebs, (feats, qual) = streamed[int(rng.integers(len(streamed)))]
        r = int(rng.integers(feats.shape[0]))
        out = np.concatenate([np.asarray(feats[r]), np.asarray(qual[r])], -1)
        rows.append((st["source"].read_rows(name, r, r + 1)[0],
                     np.asarray(ebs, np.float32), out))
    kept = {"rows": rows, "runs": st["runs"], "expected": st["expected"]}
    shutil.rmtree(st["tmp"], ignore_errors=True)
    st.clear()
    return kept


def _runs_numbers(runs, expected: int, ctx, lowered: bool = False) -> dict:
    """Worst error-bound ratio and relative ratio gap over the training
    runs, of which ``expected`` were to be recorded.  ``lowered`` puts the program's compressor, run on the slice
    rounded to bfloat16, in the timed run's place (the control)."""
    import ml_dtypes
    from repro import compressors as C
    S = ctx.lib("ref/sizes")
    missing = 1e9                 # a run or ratio that was not recorded
    out = {"bound_ratio": 0.0, "cr_rel": 0.0}
    for name, x, eps, codes, aux, cr in runs:
        comp = C.get(name)
        ref = S.ratio(x, S.size_bytes(name, codes, aux, eps))
        if lowered:
            xl = x.astype(ml_dtypes.bfloat16).astype(np.float32)
            codes, aux = comp.encode(xl, eps)
            cr = S.ratio(x, S.size_bytes(name, codes, aux, eps))
        recon = np.asarray(comp.decode(codes, aux, eps))
        gap = abs(cr / ref - 1.0) if np.isfinite(cr) else missing
        out["bound_ratio"] = max(out["bound_ratio"],
                                 S.bound_ratio(x, recon, eps))
        out["cr_rel"] = max(out["cr_rel"], gap)
    if len(runs) < expected or not runs:
        out = {k: missing for k in out}
    return out


def check(kept: dict, ctx) -> dict:
    """Worst deviation of the sampled streamed rows from the reference,
    and of the recorded training runs from theirs."""
    vf = float(ctx.cell.config["variance_fraction"])
    return dict(ctx.lib("ref/oracle").compare(kept["rows"], vf),
                **_runs_numbers(kept["runs"], kept["expected"], ctx))


def control(kept: dict, ctx) -> dict:
    """The same comparison with the control in the program's place."""
    vf = float(ctx.cell.config["variance_fraction"])
    return dict(ctx.lib("ref/oracle").compare(kept["rows"], vf,
                                              control=True),
                **_runs_numbers(kept["runs"], kept["expected"], ctx,
                                lowered=True))


def fault(kept: dict, ctx) -> dict:
    """The same comparison where every answer is another one's: each
    streamed row and each training ratio handed to its neighbour."""
    rows, runs = kept["rows"], kept["runs"]
    rows = [r[:2] + rows[(i + 1) % len(rows)][2:] for i, r in
            enumerate(rows)]
    runs = [r[:5] + runs[(i + 1) % len(runs)][5:] for i, r in
            enumerate(runs)]
    return check(dict(kept, rows=rows, runs=runs), ctx)
