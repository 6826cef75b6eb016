#!/usr/bin/env python3
"""Run the predictor sweep, the advisor and the sweep service once on a TPU.

    python chip_smoke.py [--seed 0]        # one chip
    python chip_smoke.py --four-chips      # the sharded sweep and service

One chip: fields are generated from ``--seed`` at the paper's shapes
(Miranda 256 x 384 x 384 slices, Hurricane 100 x 500 x 500 slices, one
Miranda 256 x 384 x 384 volume).  ``features_sweep(mode="both")`` runs
on each through the jnp route and the Pallas route; rows of each are
checked against a NumPy oracle (f32 quantizer decisions, f64 everything
else), and the two routes are compared.  Then the advisor CLI runs in
process on a dataset written by ``tools/make_dataset.py``, and a
``SweepService`` answers featurize, find_eb, best_compressor and
find_setting requests that must equal the direct library calls.

``--four-chips`` runs only the sharded sweep (k divisible by 4, and a
ragged k = 255) and a ``SweepService`` on a 4-device mesh, each against
the single-device result.

Earlier lines are the report: device kind, compile seconds, peak device
memory and each check's deviation (no line is a device timing).  The
last line is one JSON object naming the device.  A failed check exits
non-zero before that line, and so does a run that finds no TPU.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import math
import os
import sys
import tempfile
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

FIELDS_2D = (("miranda-vx", 256, 384), ("hurricane-u", 100, 500))
VOLUME = ("miranda-vx", (256, 384, 384))
ADVISOR_VARS = ("miranda-vx:8:384", "miranda-de:8:384", "hurricane-u:8:500")
N_EBS = 6
EB_TOP = 1e-2                 # largest eb, as a fraction of the value range
CHECK_ROWS = 4                # oracle rows per stack
KERNELS = ("gram_batched", "qent_histogram", "quality_sse")
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
U32 = 2.0 ** -24              # f32 unit roundoff


def say(msg: str) -> None:
    print(msg, flush=True)


def check(name: str, ok: bool, detail: str) -> None:
    say(f"  [{'ok' if ok else 'FAIL'}] {name}: {detail}")
    if not ok:
        raise SystemExit(f"chip_smoke: check failed: {name}")


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (a persistent
    cache hit counts as its retrieval), summed from jax.monitoring."""

    def __init__(self, jax):
        self.s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.s += duration


def peak_bytes(device) -> int:
    return int(device.memory_stats()["peak_bytes_in_use"])


# ---------------------------------------------------------------------------
# NumPy oracle
# ---------------------------------------------------------------------------

def codes_f32(flat: np.ndarray, eps: np.float32) -> np.ndarray:
    """The quantizer's codes: floor(x / eps) in f32, int32-saturated."""
    from repro.quant import INT32_CODE_MAX, INT32_CODE_MIN
    q = np.floor(flat / eps)
    return np.clip(q, INT32_CODE_MIN, INT32_CODE_MAX).astype(np.int64)


def entropy_f64(codes: np.ndarray) -> float:
    _, c = np.unique(codes, return_counts=True)
    p = c / codes.size
    return float(-np.sum(p * np.log2(p)))


def truncation_f64(x: np.ndarray, vf: float) -> tuple[float, float]:
    """(fraction of eigenvalues reaching ``vf`` of the mass, the fraction
    one eigenvalue is worth) of a 2-D slice, or the mean over modes of a
    volume, from f64 Gram matrices."""
    x = x.astype(np.float64)
    if x.ndim == 2:
        x = x - x.mean(axis=0, keepdims=True)
        unfoldings = [x]
    else:
        x = x - x.mean()
        unfoldings = [np.moveaxis(x, m, 0).reshape(x.shape[m], -1)
                      for m in range(x.ndim)]
    fracs, steps = [], []
    for u in unfoldings:
        p, q = u.shape
        g = u.T @ u if p >= q else u @ u.T
        ev = np.clip(np.linalg.eigvalsh(g), 0.0, None)[::-1]
        cum = np.cumsum(ev) / ev.sum()
        fracs.append((1 + int(np.sum(cum < vf))) / ev.size)
        steps.append(1.0 / ev.size)
    return float(np.mean(fracs)), float(np.mean(steps))


def quality_f64(flat: np.ndarray, eps: np.float32, tile: int):
    """(PSNR dB, NRMSE, PSNR tolerance dB, NRMSE relative tolerance).

    The oracle takes the quantizer's f32 code decisions and computes the
    dequantization error exactly, in f64.  The device computes it in f32:
    each element's error is off by at most one ulp ``a`` of the data's
    magnitude, so its square is off by at most ``2|e|a + a^2``; the f32
    SSE adds a balanced tree inside each tile and tiles in sequence."""
    codes = codes_f32(flat, eps)
    x = flat.astype(np.float64)
    e = x - codes * np.float64(eps)
    mse = float(np.mean(e * e))
    rng = float(x.max() - x.min())
    a = float(np.spacing(np.float32(np.abs(x).max())))
    delta = (sse_rounding(flat.size, tile)
             + (2 * a * float(np.mean(np.abs(e))) + a * a) / mse)
    return (20 * math.log10(rng) - 10 * math.log10(mse),
            math.sqrt(mse) / rng) + quality_tolerance(delta)


def sse_rounding(n: int, tile: int) -> float:
    """Relative bound on the f32 SSE's summation error."""
    return (math.ceil(n / tile) + math.log2(tile) + 3) * U32


def quality_tolerance(delta: float) -> tuple[float, float]:
    """(PSNR dB, NRMSE relative) bounds for an SSE off by ``delta``
    relative, plus f32 rounding of the finalization."""
    return -10 * math.log10(1 - min(delta, 0.5)) + 2e-4, delta / 2 + 1e-6


def qent_tolerance(n: int) -> float:
    """Bits: f32 evaluation of H = log2(n) - sum(c log2 c) / n."""
    return 64 * U32 * math.log2(n)


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def make_stacks(seed: int):
    import jax
    from repro.data import scientific as SC
    stacks = {}
    for name, k, n in FIELDS_2D:
        stacks[name] = (name, SC.field_slices(name, count=k, seed=seed, n=n))
    name, shape = VOLUME
    stacks[f"{name} volume"] = (name, SC.volume(name, shape, seed=seed)[None])
    for _, x in stacks.values():
        jax.block_until_ready(x)
    return stacks


def eb_grid(field: str, x) -> np.ndarray:
    from repro.data import scientific as SC
    rng = float(x.max() - x.min())
    return np.geomspace(SC.FIELDS[field].eps, EB_TOP * rng,
                        N_EBS).astype(np.float32)


def compiled_kernels(P, x, ebs, cfg) -> list:
    """Kernel names present as tpu_custom_call in the compiled sweep."""
    import jax.numpy as jnp
    txt = P._features_sweep_traced.lower(
        x, jnp.asarray(ebs), vf=P.variance_fraction_for(cfg, x.ndim),
        bins=cfg.qent_bins, use_kernels=True, tune=cfg.tune,
        mode="both").compile().as_text()
    calls = [ln for ln in txt.splitlines() if "tpu_custom_call" in ln]
    return [k for k in KERNELS if any(k in ln for ln in calls)]


def check_histogram(rows: np.ndarray, ebs: np.ndarray, bins: int) -> None:
    """The qent kernel's histograms against the oracle's codes, exactly."""
    import jax.numpy as jnp
    from repro.kernels.qent import qent as QK
    tile = QK.DEFAULT_TILE
    flat = rows.reshape(rows.shape[0], -1)
    pad = (-flat.shape[1]) % tile
    flat = np.concatenate([flat, np.repeat(flat[:, :1], pad, axis=1)], 1)
    hist = np.asarray(QK.qent_histogram_sweep(
        jnp.asarray(flat), jnp.asarray(ebs), tile=tile, bins=bins))
    bad = fit = 0
    for r in range(flat.shape[0]):
        for e, eps in enumerate(ebs):
            codes = codes_f32(flat[r], eps)
            want = np.bincount(codes & (bins - 1), minlength=bins)
            bad += int(np.abs(hist[r, e] - want).sum())
            if codes.max() - codes.min() < bins:
                fit += 1
                _, counts = np.unique(codes, return_counts=True)
                bad += int(not np.array_equal(
                    np.sort(hist[r, e][hist[r, e] > 0]), np.sort(counts)))
    check("qent kernel histogram", bad == 0,
          f"{flat.shape[0]}x{len(ebs)} histograms of {bins} bins, "
          f"{fit} with codes that fit the bins; miscounted elements {bad}")


def oracle_rows(x, ebs, vf: float, bins: int) -> dict:
    """The oracle on CHECK_ROWS rows of a stack, computed once for both
    routes: per row the truncation fraction, sigma, and per eb the
    q-ent, whether the codes fit ``bins``, PSNR, NRMSE and their
    tolerances."""
    from repro.kernels.quality import ref as QR
    k = x.shape[0]
    rows = sorted({0, k // 3, (2 * k) // 3, k - 1})[:CHECK_ROWS]
    out = {}
    for r in rows:
        xr = np.asarray(x[r], np.float32)
        flat = xr.reshape(-1)
        frac, step = truncation_f64(xr, vf)
        per_eb = []
        for eps in ebs:
            codes = codes_f32(flat, eps)
            per_eb.append((entropy_f64(codes),
                           bool(codes.max() - codes.min() < bins))
                          + quality_f64(flat, eps, QR.DEFAULT_TILE))
        out[r] = (frac, step, float(flat.astype(np.float64).std()), per_eb)
    return out


def check_rows(label: str, out: np.ndarray, oracle: dict, hashed: bool,
               n: int) -> None:
    """One route's (k, e, 4) tensor against the oracle rows.  ``hashed``:
    the route's q-ent histogram hashes codes into bins, so rows whose
    codes overflow the bins are skipped for q-ent (collisions there only
    lower the entropy, by design)."""
    worst = {"qent": 0.0, "trunc": 0.0, "psnr": 0.0, "nrmse": 0.0}
    skipped = 0
    tol_q = qent_tolerance(n)
    for r, (frac, step, sigma, per_eb) in oracle.items():
        sv = math.exp(float(out[r, 0, 1])) * sigma
        d = abs(sv - frac) / step            # in eigenvalues (per mode)
        worst["trunc"] = max(worst["trunc"], d)
        if d > 1.0 + 1e-3:
            check(f"{label} row {r} truncation", False,
                  f"device {sv:.6f} vs f64 {frac:.6f} ({d:.3f} eigenvalues)")
        for e, (qent, fits, psnr, nrmse, tol_p, tol_n) in enumerate(per_eb):
            if hashed and not fits:
                skipped += 1
            else:
                dq = abs(math.exp(float(out[r, e, 0])) - qent)
                worst["qent"] = max(worst["qent"], dq)
                if dq > tol_q:
                    check(f"{label} row {r} eb {e} q-ent", False,
                          f"|d| {dq:.3g} bits (tol {tol_q:.3g})")
            dp = abs(float(out[r, e, 2]) - psnr)
            dn = abs(float(out[r, e, 3]) - nrmse) / nrmse
            worst["psnr"] = max(worst["psnr"], dp)
            worst["nrmse"] = max(worst["nrmse"], dn)
            if dp > tol_p or dn > tol_n:
                check(f"{label} row {r} eb {e} quality", False,
                      f"PSNR |d| {dp:.3g} dB (tol {tol_p:.3g}), NRMSE rel "
                      f"|d| {dn:.3g} (tol {tol_n:.3g})")
    check(f"{label} vs oracle", True,
          f"rows {list(oracle)}: worst q-ent |d| {worst['qent']:.3g} bits "
          f"(tol {tol_q:.3g}; {skipped} of {len(oracle) * N_EBS} skipped, "
          f"codes overflow the bins), truncation {worst['trunc']:.3g} "
          f"eigenvalues (tol 1), PSNR {worst['psnr']:.3g} dB, NRMSE rel "
          f"{worst['nrmse']:.3g}")


def compare_routes(label: str, a: np.ndarray, b: np.ndarray, fits) -> None:
    """Report (never hide) how far the Pallas route is from the jnp one."""
    names = ("log q-ent", "log trunc/sigma", "PSNR", "NRMSE")
    parts = []
    for c, name in enumerate(names):
        da, db = (a[:, fits, c], b[:, fits, c]) if c == 0 else (a[..., c],
                                                                b[..., c])
        same = np.array_equal(da.view(np.uint32), db.view(np.uint32))
        diff = float(np.max(np.abs(da - db))) if da.size else 0.0
        parts.append(f"{name} {'bit-equal' if same else f'max |d| {diff:.3g}'}")
    say(f"  {label} pallas vs jnp: " + ", ".join(parts))


def run_sweeps(stacks, clock, dev) -> None:
    from repro.core import predictors as P
    routes = (("jnp", P.PredictorConfig()),
              ("pallas", P.PredictorConfig(use_kernels=True)))
    for label, (field, x) in stacks.items():
        ebs = eb_grid(field, x)
        say(f"sweep {label} {tuple(x.shape)}, ebs {np.array2string(ebs, precision=3)}")
        pcfg = routes[1][1]
        oracle = oracle_rows(x, ebs, P.variance_fraction_for(pcfg, x.ndim),
                             pcfg.qent_bins)
        outs = {}
        for route, cfg in routes:
            c0, t0 = clock.s, time.perf_counter()
            feats, qual = P.features_sweep(x, ebs, cfg, quality=True)
            out = np.concatenate([np.asarray(feats), np.asarray(qual)], -1)
            wall = time.perf_counter() - t0
            finite = bool(np.isfinite(out).all())
            check(f"{label} {route} shape and finiteness",
                  out.shape == (x.shape[0], N_EBS, 4) and finite,
                  f"{out.shape}, finite={finite}; compile "
                  f"{clock.s - c0:.1f} s, first call host wall {wall:.1f} s, "
                  f"peak_bytes_in_use {peak_bytes(dev)}")
            check_rows(f"{label} {route}", out, oracle, cfg.use_kernels,
                       int(np.prod(x.shape[1:])))
            outs[route] = out
        found = compiled_kernels(P, x, ebs, pcfg)
        check(f"{label} kernels in compiled HLO", found == list(KERNELS),
              f"tpu_custom_call for {found}")
        rows = np.asarray(x[:2] if x.shape[0] > 1 else x, np.float32)
        check_histogram(rows, ebs, pcfg.qent_bins)
        fits = np.all([[f for _, f, *_ in per_eb]
                       for *_, per_eb in oracle.values()], axis=0)
        compare_routes(label, outs["jnp"], outs["pallas"], fits)


def run_advisor(seed: int, tmp: str) -> None:
    from repro.launch import advise as ADV
    spec = importlib.util.spec_from_file_location(
        "make_dataset", os.path.join(ROOT, "tools", "make_dataset.py"))
    mk = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mk)
    var_args = [a for v in ADVISOR_VARS for a in ("--var", v)]
    ds = mk.main([os.path.join(tmp, "ds"), *var_args, "--seed", str(seed)])
    report = ADV.main([ds, "--compressors", "sz3-interp,zfp",
                       "--targets", "4,8,16", "--psnr-floor", "60"])
    for name, var in report["variables"].items():
        feasible = {t: r["compressor"] for t, r in var["targets"].items()
                    if r["feasible"]}
        check(f"advisor {name}", bool(feasible),
              f"feasible targets {feasible} of {sorted(var['targets'])}")


def run_service(x, ebs) -> None:
    import jax.numpy as jnp
    from repro.core import predictors as P, usecases as UC
    from repro.serve.sweep_service import ServiceConfig, SweepService
    grid = ebs[1:5].astype(np.float64)
    k = x.shape[0]
    train = x[::max(1, k // 8)][:8]      # spans the stack's range of rows
    models = {c: UC.EbGridModel.train(train, c, grid)
              for c in ("sz3-interp", "zfp")}
    uc2 = {c: m.models[1] for c, m in models.items()}
    with SweepService(ServiceConfig()) as svc:
        rows = x[k // 2:k // 2 + 8]
        got = svc.featurize(rows, ebs)
        want = np.asarray(P.features_sweep(jnp.asarray(rows), ebs))
        check("service featurize", np.array_equal(got, want),
              f"{got.shape}, max |d| {float(np.max(np.abs(got - want))):.3g}")
        for i in (k // 3 + 1, k - 3):
            q = x[i]
            got, want = svc.find_eb(models["zfp"], q, 8.0), \
                UC.find_error_bound_for_cr(models["zfp"], q, 8.0)
            check(f"service find_eb row {i}", got == want,
                  f"{got} vs {want}")
            got, want = svc.best_compressor(uc2, q, float(grid[1])), \
                UC.best_compressor(uc2, q, float(grid[1]))
            check(f"service best_compressor row {i}", got == want,
                  f"{got[0]} vs {want[0]}")
            got, want = svc.find_setting(models, q, 4.0, 60.0), \
                UC.find_setting(models, q, cr_floor=4.0, psnr_floor=60.0)
            check(f"service find_setting row {i}", got == want,
                  f"({got.compressor}, {got.eb}, feasible={got.feasible}) vs "
                  f"({want.compressor}, {want.eb}, feasible={want.feasible})")


def one_chip(args, jax, clock) -> None:
    dev = jax.devices()[0]
    t0 = time.perf_counter()
    stacks = make_stacks(args.seed)
    say(f"set-up: generated {', '.join(f'{k} {tuple(v[1].shape)}' for k, v in stacks.items())} "
        f"in {time.perf_counter() - t0:.1f} s host wall "
        f"(compile {clock.s:.1f} s of it)")
    run_sweeps(stacks, clock, dev)
    field, x = stacks[FIELDS_2D[0][0]]
    del stacks
    with tempfile.TemporaryDirectory() as tmp:
        c0 = clock.s
        run_advisor(args.seed, tmp)
        say(f"advisor: compile {clock.s - c0:.1f} s, "
            f"peak_bytes_in_use {peak_bytes(dev)}")
    c0 = clock.s
    run_service(x, eb_grid(field, x))
    say(f"service: compile {clock.s - c0:.1f} s, "
        f"peak_bytes_in_use {peak_bytes(dev)}")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def check_same(label: str, got: np.ndarray, want: np.ndarray, p: int,
               n: int) -> None:
    """Sharded vs single-device (k, e, 2 or 4) tensors: equal, or within
    the oracle's bounds (one eigenvalue of p for the truncation column)."""
    from repro.kernels.quality import ref as QR
    if got.shape != want.shape:
        check(label, False, f"shape {got.shape} vs {want.shape}")
    if np.array_equal(got.view(np.uint32), want.view(np.uint32)):
        check(label, True, f"{got.shape} bit-equal")
        return
    d = np.abs(got - want)
    ok = (d[..., 0].max() <= 1e-5
          and d[..., 1].max() <= math.log1p(1.0 / p) + 1e-5)
    detail = (f"{got.shape} max |d| log q-ent {d[..., 0].max():.3g}, "
              f"log trunc {d[..., 1].max():.3g}")
    if got.shape[-1] == 4:
        tol_p, tol_n = quality_tolerance(sse_rounding(n, QR.DEFAULT_TILE))
        rel_n = d[..., 3] / np.maximum(np.abs(want[..., 3]), 1e-30)
        ok = ok and d[..., 2].max() <= tol_p and rel_n.max() <= tol_n
        detail += (f", PSNR {d[..., 2].max():.3g} dB, NRMSE rel "
                   f"{rel_n.max():.3g}")
    check(label, ok, detail)


def four_chips(args, jax, clock) -> None:
    from repro.core import predictors as P
    from repro.data import scientific as SC
    from repro.launch import mesh as LM
    from repro.serve.sweep_service import ServiceConfig, SweepService
    devs = jax.devices()
    if len(devs) != 4:
        raise SystemExit(f"chip_smoke --four-chips needs 4 devices, "
                         f"found {len(devs)}")
    field, k, edge = FIELDS_2D[0]
    x = SC.field_slices(field, count=k, seed=args.seed, n=edge)
    ebs = eb_grid(field, x)
    n = edge * edge

    def both(stack, **kw):
        f, q = P.features_sweep(stack, ebs, quality=True, **kw)
        return np.concatenate([np.asarray(f), np.asarray(q)], -1)

    single = both(x, sharded=False)
    mesh = LM.make_sweep_mesh(4)
    for kk in (k, k - 1):
        c0 = clock.s
        got = both(x[:kk], mesh=mesh)
        check_same(f"sharded sweep k={kk} vs single device", got,
                   single[:kk], edge, n)
        say(f"  compile {clock.s - c0:.1f} s")
    shard = k // 4
    with SweepService(ServiceConfig(), mesh=mesh) as svc:
        # two requests of one row bucket: one sharded executable
        for lo, hi in ((0, shard), (k // 2 - 7, k // 2 - 7 + shard)):
            got = svc.featurize(x[lo:hi], ebs)
            want = np.asarray(P.features_sweep(x[lo:hi], ebs, sharded=False))
            check_same(f"4-device service featurize rows {lo}:{hi}",
                       got, want, edge, n)
    shard_bytes = shard * n * 4
    peaks = [peak_bytes(d) for d in devs]
    check("every device held its rows", min(peaks) >= shard_bytes,
          "peak_bytes_in_use " + ", ".join(
              f"device {d.id}: {p}" for d, p in zip(devs, peaks))
          + f" (a {shard}-row shard is {shard_bytes} bytes)")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded sweep and service on 4 chips")
    args = ap.parse_args()

    from repro.launch import compile_cache
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {devs[0].platform}",
              file=sys.stderr)
        sys.exit(2)
    say(f"device: {devs[0].device_kind} x{len(devs)}, jax {jax.__version__}, "
        f"compile cache {compile_cache.enable()}")
    clock = CompileClock(jax)
    t0 = time.perf_counter()
    (four_chips if args.four_chips else one_chip)(args, jax, clock)
    say(f"total: compile {clock.s:.1f} s, host wall "
        f"{time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs) if args.four_chips else 1}}), flush=True)


if __name__ == "__main__":
    main()
