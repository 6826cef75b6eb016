"""Compression advisor CLI: sweep every variable of a dataset, emit a
per-field (compressor, error bound) recommendation report.

The paper's production story (UC1 + UC2 at dataset scale): instead of
trial-and-error compressor runs, stream every variable of a file-backed
dataset through the chunked featurization sweep (``core.stream``), train
one ``EbGridModel`` per candidate compressor on a small leading sample
of each variable (the ONLY compressor executions anywhere in the run),
and report per CR target the compressor reaching it at the smallest
error bound -- the workflow enstools ships as its analyzer's
``compression="lossy,sz,abs,0.001"`` spec strings.

    python -m repro.launch.advise DATASET --targets 4,8,16 \\
        --compressors sz3-interp,zfp --budget-mb 64 --out report.json

``DATASET`` is a ``tools/make_dataset.py`` output (memmap directory or
``.npz``).  Variables larger than device memory stream within
``--budget-mb``; features are bit-equal to an in-memory sweep
(``bench_stream`` gates it).  ``--service`` routes every chunk through
an in-process ``SweepService`` ``advise`` method, so advisor traffic
rides the coalesced launches and the cross-request feature cache;
either way each variable's streaming content digest (``slice_digest``
of the never-materialized variable) lands in the report, keying future
cache hits.

Per-variable recommendation
---------------------------
Per-row predicted CRs (``AdviseMethod.cr_table``) aggregate across the
variable by HARMONIC mean per (compressor, grid eb) -- rows share one
uncompressed size, so the harmonic mean is the variable's total-bytes
CR.  Per target the eb hitting it interpolates log-log along the
(monotonized) CR-vs-eb curve; among compressors reaching the target the
SMALLEST eb (least distortion) wins, and when none reaches it the
closest-achieving compressor at the grid ceiling is reported with
``feasible: false``.

``--psnr-floor DB`` adds the quality axis (UC3): the SAME streamed pass
also emits the fused per-(row, eb) PSNR/NRMSE tensor (``quality=True``
-- one read covers both halves of the ratio-quality frontier), the
variable's worst-row PSNR curve turns the floor into an eb ceiling, and
recommendations only call a setting feasible when it meets the CR
target INSIDE the quality-feasible region.
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, Optional

import numpy as np

from repro import compressors as C
from repro import obs
from repro.core import stream as ST
from repro.core import usecases as UC
from repro.core.predictors import PredictorConfig
from repro.data import source as SRC
from repro.serve.method import AdviseMethod

DEFAULT_GRID_RELS = (1e-4, 1e-3, 1e-2)
DEFAULT_TARGETS = (4.0, 8.0, 16.0)


def harmonic_cr(cr_rows: np.ndarray) -> np.ndarray:
    """(k, n_comp, e) per-row CRs -> (n_comp, e) variable-level CRs.
    Rows have equal uncompressed size, so total_bytes / total_compressed
    is the harmonic mean of the per-row ratios."""
    return cr_rows.shape[0] / np.sum(1.0 / np.maximum(cr_rows, 1e-12),
                                     axis=0)


def eb_for_target(ebs: np.ndarray, crs: np.ndarray,
                  target: float) -> Optional[tuple[float, float]]:
    """Smallest grid-interpolated eb at which the (monotonized) CR curve
    reaches ``target``; None when even the grid ceiling falls short.
    Returns (eb, predicted_cr at that eb)."""
    mono = np.maximum.accumulate(np.maximum(crs, 1e-12))
    if target > mono[-1]:
        return None
    if target <= mono[0]:
        return float(ebs[0]), float(mono[0])
    le = float(np.interp(np.log(target), np.log(mono), np.log(ebs)))
    cr = float(np.exp(np.interp(le, np.log(ebs), np.log(mono))))
    return float(np.exp(le)), cr


def recommend(names, ebs: np.ndarray, var_cr: np.ndarray, targets, *,
              psnr_floor: Optional[float] = None,
              var_psnr: Optional[np.ndarray] = None) -> Dict[str, dict]:
    """Per-target pick from a (n_comp, e) variable CR table: the
    feasible compressor with the smallest eb, else the closest.

    With ``psnr_floor`` + ``var_psnr`` (the variable's worst-row PSNR
    per grid eb, compressor-independent -- it measures the quantization
    proxy), the pick is UC3-shaped: PSNR is monotonized nonincreasing in
    eb, the floor becomes an eb CEILING (the largest log-eb still
    meeting it), and only settings at or below the ceiling count as
    feasible.  Each recommendation then also reports ``predicted_psnr``
    at its eb and ``psnr_ok``.  When the floor is unreachable even at
    the finest grid eb every target is infeasible and reports the
    finest-eb setting (the least-distorted achievable one)."""
    lg = np.log(ebs)
    le_ceil = None
    pm = None
    if psnr_floor is not None and var_psnr is not None:
        pm = np.minimum.accumulate(np.asarray(var_psnr, np.float64))
        if pm[0] < psnr_floor:
            out = {}
            for t in targets:
                ci = int(np.argmax(var_cr[:, 0]))
                out[f"{float(t):g}"] = {
                    "compressor": names[ci], "eb": float(ebs[0]),
                    "predicted_cr": float(var_cr[ci, 0]),
                    "predicted_psnr": float(pm[0]), "psnr_ok": False,
                    "feasible": False}
            return out
        if pm[-1] >= psnr_floor:
            le_ceil = float(lg[-1])
        else:
            # pm is nonincreasing: reversed it is nondecreasing, the
            # shape np.interp wants
            le_ceil = float(np.interp(psnr_floor, pm[::-1], lg[::-1]))

    def psnr_at(le: float) -> Optional[float]:
        return None if pm is None else float(np.interp(le, lg, pm))

    out: Dict[str, dict] = {}
    for t in targets:
        hits = []
        for ci, name in enumerate(names):
            hit = eb_for_target(ebs, var_cr[ci], float(t))
            if hit is None:
                continue
            if le_ceil is not None and np.log(hit[0]) > le_ceil + 1e-12:
                continue                # reaches the CR only past the floor
            hits.append((hit[0], name, hit[1]))
        if hits:
            eb, name, cr = min(hits)
            rec = {"compressor": name, "eb": eb,
                   "predicted_cr": cr, "feasible": True}
        elif le_ceil is None:
            ci = int(np.argmax(var_cr[:, -1]))
            rec = {"compressor": names[ci], "eb": float(ebs[-1]),
                   "predicted_cr": float(var_cr[ci, -1]), "feasible": False}
        else:
            # best achievable CR inside the quality-feasible region:
            # CR is (monotonized) nondecreasing in eb, so it sits at the
            # ceiling itself
            le_cap = min(le_ceil, float(lg[-1]))
            caps = [float(np.exp(np.interp(
                le_cap, lg,
                np.log(np.maximum.accumulate(np.maximum(var_cr[ci], 1e-12))))))
                for ci in range(len(names))]
            ci = int(np.argmax(caps))
            rec = {"compressor": names[ci], "eb": float(np.exp(le_cap)),
                   "predicted_cr": caps[ci], "feasible": False}
        if pm is not None:
            p = psnr_at(float(np.log(rec["eb"])))
            rec["predicted_psnr"] = p
            rec["psnr_ok"] = bool(p >= psnr_floor - 1e-9)
        out[f"{float(t):g}"] = rec
    return out


def advise_variable(source: SRC.DatasetSource, name: str, *,
                    compressors, grid_rels, targets, train_rows: int,
                    cfg: PredictorConfig, stream: ST.StreamConfig,
                    mesh=None, service=None,
                    psnr_floor: Optional[float] = None) -> dict:
    """Train sample models + stream the full variable -> report entry.

    ``psnr_floor``: also stream the fused quality tensor (same pass,
    ``quality=True`` -- on the service path each chunk pairs its advise
    submission with a ``submit_quality`` riding the same batch windows)
    and recommend only quality-feasible settings (see
    :func:`recommend`)."""
    meta = source.meta(name)
    with obs.span("repro.advise.variable", variable=name, rows=meta.rows,
                  nbytes=meta.nbytes_f32):
        ndim = len(meta.shape) - 1
        sample = source.read_rows(name, 0, min(int(train_rows), meta.rows))
        rng = float(np.max(sample) - np.min(sample))
        if rng <= 0:
            return {"shape": list(meta.shape), "skipped": "constant sample"}
        ebs = np.asarray([r * rng for r in grid_rels], np.float64)

        # the ONLY compressor executions of the whole run: the training
        # sample (the paper's UC1/UC2 speedup structure -- everything
        # else is predictor sweeps + model evaluations)
        models = {}
        for comp in compressors:
            with obs.span("repro.advise.train", compressor=comp):
                models[comp] = UC.EbGridModel.train(sample, comp, ebs,
                                                    cfg=cfg, ndim=ndim)

        digest = SRC.StreamingDigest()
        var_psnr = None
        with obs.span("repro.advise.stream"):
            if service is not None:
                # chunks ride the service's coalesced launches; futures
                # overlap the next chunk's read exactly like the direct
                # driver's in-flight window.  With a quality floor each
                # chunk pairs its advise submission with a quality
                # submission over the same rows/ebs, riding the same
                # batch windows.
                futs, qfuts = [], []
                for _, chunk in source.chunks(
                        name, budget_bytes=stream.budget_bytes):
                    digest.update(chunk)
                    futs.append(service.submit_advise(models, chunk))
                    if psnr_floor is not None:
                        qfuts.append(service.submit_quality(chunk, ebs, cfg))
                cr_rows = np.concatenate([f.result()["cr"] for f in futs],
                                         axis=0)
                if qfuts:
                    qual = np.concatenate([f.result() for f in qfuts],
                                          axis=0)
                    var_psnr = qual[:, :, 0].min(axis=0)
            elif psnr_floor is not None:
                feats, qual = ST.stream_features(
                    source, name, ebs, cfg, stream=stream, mesh=mesh,
                    digest=digest, quality=True)
                # worst row per eb: the variable meets the floor only
                # when every row does
                var_psnr = np.asarray(qual)[:, :, 0].min(axis=0)
            else:
                feats = ST.stream_features(source, name, ebs, cfg,
                                           stream=stream, mesh=mesh,
                                           digest=digest)

        with obs.span("repro.advise.recommend"):
            if service is None:
                cr_rows = AdviseMethod.cr_table(models, feats)
            var_cr = harmonic_cr(cr_rows)
            names = tuple(models)
            entry = {
                "shape": list(meta.shape), "rows": meta.rows,
                "digest": digest.digest(),
                "eb_grid": [float(e) for e in ebs],
                "value_range": rng,
                "cr_by_compressor": {n: [float(c) for c in var_cr[i]]
                                     for i, n in enumerate(names)},
                "targets": recommend(names, ebs, var_cr, targets,
                                     psnr_floor=psnr_floor,
                                     var_psnr=var_psnr),
            }
            if var_psnr is not None:
                entry["psnr_floor"] = float(psnr_floor)
                entry["psnr_by_eb"] = [float(p) for p in var_psnr]
        return entry


def advise_dataset(source: SRC.DatasetSource, *, compressors=None,
                   grid_rels=DEFAULT_GRID_RELS, targets=DEFAULT_TARGETS,
                   train_rows: int = 6,
                   cfg: PredictorConfig = PredictorConfig(),
                   stream: Optional[ST.StreamConfig] = None,
                   mesh=None, service=None,
                   fields=None,
                   psnr_floor: Optional[float] = None) -> dict:
    """The advisor as a library call (the CLI and ``bench_stream`` both
    route here).  Returns the full report dict."""
    stream = stream if stream is not None else ST.StreamConfig()
    report: dict = {"targets": [float(t) for t in targets],
                    "budget_bytes": stream.budget_bytes, "variables": {}}
    if psnr_floor is not None:
        report["psnr_floor"] = float(psnr_floor)
    for name in (fields if fields else source.variables()):
        meta = source.meta(name)
        comps = compressors if compressors else (
            C.STUDY_2D if len(meta.shape) == 3 else C.STUDY_3D)
        report["variables"][name] = advise_variable(
            source, name, compressors=comps, grid_rels=grid_rels,
            targets=targets, train_rows=train_rows, cfg=cfg,
            stream=stream, mesh=mesh, service=service,
            psnr_floor=psnr_floor)
    return report


def _print_report(report: dict, file=sys.stdout) -> None:
    print(f"# advisor report  (chunk budget "
          f"{report['budget_bytes'] / 2**20:.1f} MiB)", file=file)
    for name, var in report["variables"].items():
        if "skipped" in var:
            print(f"{name}: skipped ({var['skipped']})", file=file)
            continue
        print(f"{name}  shape={tuple(var['shape'])}  "
              f"digest={var['digest'][:12]}", file=file)
        for t, rec in var["targets"].items():
            note = "" if rec["feasible"] else "  (best achievable)"
            q = ""
            if "predicted_psnr" in rec:
                mark = "" if rec["psnr_ok"] else " <floor"
                q = f"  psnr={rec['predicted_psnr']:.1f}dB{mark}"
            print(f"  CR>={t:>4}: {rec['compressor']:<16} "
                  f"eb={rec['eb']:.3e}  predicted_cr={rec['predicted_cr']:.2f}"
                  f"{q}{note}", file=file)


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(
        prog="python -m repro.launch.advise",
        description="Per-field compression recommendations for a "
                    "file-backed dataset via streamed predictor sweeps.")
    ap.add_argument("dataset", help="memmap dataset dir or .npz archive "
                                    "(tools/make_dataset.py output)")
    ap.add_argument("--fields", default="",
                    help="comma-separated variable subset (default: all)")
    ap.add_argument("--compressors", default="",
                    help="comma-separated candidate set (default: the "
                         "full STUDY_2D/STUDY_3D set per variable rank)")
    ap.add_argument("--targets", default=",".join(
        f"{t:g}" for t in DEFAULT_TARGETS),
        help="comma-separated CR targets")
    ap.add_argument("--grid-rels", default=",".join(
        f"{r:g}" for r in DEFAULT_GRID_RELS),
        help="eb grid as fractions of each variable's value range")
    ap.add_argument("--train-rows", type=int, default=6,
                    help="leading rows per variable the models train on "
                         "(the only compressor executions)")
    ap.add_argument("--psnr-floor", type=float, default=None,
                    help="minimum acceptable PSNR (dB) of the "
                         "quantization proxy; recommendations then pick "
                         "the cheapest quality-feasible setting (UC3)")
    ap.add_argument("--budget-mb", type=float, default=64.0,
                    help="per-chunk f32 byte budget (device memory cap)")
    ap.add_argument("--prefetch", type=int, default=2,
                    help="chunks the reader stages ahead (0 = synchronous)")
    ap.add_argument("--mesh", default="auto",
                    help="'auto' (all devices when >1), 'none', or a "
                         "device count")
    ap.add_argument("--service", action="store_true",
                    help="route chunks through an in-process SweepService "
                         "advise method (coalesced launches + feature "
                         "cache)")
    ap.add_argument("--out", default="", help="write the JSON report here")
    args = ap.parse_args(argv)

    from repro.launch import compile_cache
    compile_cache.enable()
    mesh = None
    if args.mesh != "none":
        import jax
        from repro.launch import mesh as M
        n = len(jax.devices()) if args.mesh == "auto" else int(args.mesh)
        if n > 1:
            mesh = M.make_sweep_mesh(n)

    source = SRC.open_dataset(args.dataset)
    stream = ST.StreamConfig(budget_bytes=int(args.budget_mb * 2**20),
                             prefetch=args.prefetch)
    fields = [f for f in args.fields.split(",") if f]
    comps = [c for c in args.compressors.split(",") if c]
    targets = [float(t) for t in args.targets.split(",") if t]
    grid_rels = sorted(float(r) for r in args.grid_rels.split(",") if r)

    svc = None
    if args.service:
        from repro.serve.sweep_service import ServiceConfig, SweepService
        svc = SweepService(ServiceConfig(), mesh=mesh)
    try:
        report = advise_dataset(
            source, compressors=comps or None, grid_rels=grid_rels,
            targets=targets, train_rows=args.train_rows, stream=stream,
            mesh=mesh, service=svc, fields=fields or None,
            psnr_floor=args.psnr_floor)
    finally:
        if svc is not None:
            svc.close()
    _print_report(report)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
    return report


if __name__ == "__main__":
    main()
