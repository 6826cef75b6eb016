"""Backend-keyed autotuner for the gram and qent Pallas kernels.

The kernels ship with MXU-shaped defaults (``gram.DEFAULT_BN/BK = 128``,
``qent.DEFAULT_TILE = 2048``) that are right for the TPU systolic array
but leave real speed on the table elsewhere -- on the CPU/interpret
backend the qent compare tile dominates cache behavior and the gram
output tile sets the grid overhead.  This module searches the (tile,
block) grid per ``(backend_kind, dtype, shard-size bucket)`` cell, times
candidates with compile-excluded median-of-N (``block_until_ready``),
and persists the winners to ``kernels/tuned/<backend>.json``; a CPU
table generated by this tool is checked in as the committed baseline.

Two hard rules shape the search:

* **Bit-safety filter.**  A candidate is evaluated at the *ops* level
  (so padding differences are covered) and discarded unless its output
  is bitwise identical to the default configuration's output.  For gram
  this kills every ``bk != DEFAULT_BK`` candidate (the contraction tile
  sets the f32 accumulation order); qent's integer histograms make all
  tile choices bit-exact.  The table can therefore never change
  numerics -- the repo's bit-equality gates stay meaningful.
* **Hysteresis.**  A winner must beat the default by >2%% (median) or
  the cell records the default -- tiny wins are noise, and the
  committed table should only contain reproducible speedups.

Kernel entry points consult the table through :class:`TuneConfig`
(plumbed ``PredictorConfig -> FeaturizationEngine -> dist/sweep ->
serve`` launchers) with precedence *explicit kwarg > TuneConfig field >
table cell > hardcoded default*; an unknown backend with no table falls
back to today's defaults bit-exactly.

``python -m repro.kernels.tune`` runs the search (``--smoke`` for the
CI 2-cell interpret-mode grid, ``--check`` for the staleness gate).
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
import time
from typing import Any, Dict, Optional, Tuple

SCHEMA_VERSION = 1

# Per-backend peaks for the roofline report.  tpu-v5e: Google Cloud
# documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM per chip).
# cpu: nominal dev-box numbers; ``benchmarks/roofline.py`` replaces its
# bandwidth with a measured STREAM add.  A kind not listed here is an
# error: a report against another device's peaks would be wrong.
BACKEND_HW: Dict[str, Dict[str, float]] = {
    "tpu-v5e": {"peak_flops": 197e12, "mem_bw": 819e9},
    "cpu":     {"peak_flops": 5e11, "mem_bw": 20e9},
}

_ALIASES = {"tpu-v5-lite": "tpu-v5e", "tpu-v5lite": "tpu-v5e",
            "tpu-v5litepod": "tpu-v5e"}


def backend_kind() -> str:
    """Stable backend key: ``cpu``/``gpu``, or ``tpu-<generation>``."""
    import jax
    plat = jax.default_backend()
    if plat != "tpu":
        return plat
    kind = jax.devices()[0].device_kind.lower().replace(" ", "-")
    if not kind.startswith("tpu"):
        kind = f"tpu-{kind}"
    return _ALIASES.get(kind, kind)


def hw_for(kind: Optional[str] = None) -> Dict[str, float]:
    """Peaks for a backend kind (default: the running backend).  Raises
    ``ValueError`` for a kind the table does not list."""
    kind = kind or backend_kind()
    kind = _ALIASES.get(kind, kind)
    if kind not in BACKEND_HW:
        raise ValueError(f"no hardware peaks for backend kind {kind!r}; "
                         f"known: {sorted(BACKEND_HW)}")
    return BACKEND_HW[kind]


# ---------------------------------------------------------------------------
# TuneConfig + table-backed resolution


@dataclasses.dataclass(frozen=True)
class TuneConfig:
    """Kernel tile selection policy (hashable: rides jit static args and
    ``PredictorConfig``).

    ``None`` fields defer to the tuned table (when ``use_table``) and
    then to the kernel defaults; a set field wins over the table but
    loses to an explicit ``bn=``/``bk=``/``tile=`` kwarg at the call
    site.  ``backend`` pins the table key (default: autodetect)."""
    gram_bn: Optional[int] = None
    gram_bk: Optional[int] = None
    qent_tile: Optional[int] = None
    use_table: bool = True
    backend: Optional[str] = None


DEFAULT = TuneConfig()


def _bucket_p2(x: int) -> int:
    """Shard-size bucket: next power of two (cells generalize across the
    ragged shapes the service actually pads to)."""
    p = 1
    while p < x:
        p *= 2
    return p


def gram_key(m: int, n: int) -> str:
    return f"gram:f32:m{_bucket_p2(m)}:n{_bucket_p2(n)}"


def qent_key(n: int, bins: int) -> str:
    return f"qent:f32:n{_bucket_p2(n)}:b{bins}"


def table_path(backend: str) -> str:
    base = os.environ.get("REPRO_TUNED_DIR") or os.path.join(
        os.path.dirname(__file__), "tuned")
    return os.path.join(base, f"{backend}.json")


@functools.lru_cache(maxsize=16)
def _load_table_cached(backend: str, path: str) -> Optional[Dict[str, Any]]:
    try:
        with open(path) as f:
            table = json.load(f)
    except FileNotFoundError:
        return None
    except (json.JSONDecodeError, OSError) as e:  # corrupt: warn, fall back
        import warnings
        warnings.warn(f"ignoring unreadable tuned table {path}: {e}")
        return None
    if not isinstance(table, dict) or \
            table.get("schema_version") != SCHEMA_VERSION:
        import warnings
        warnings.warn(
            f"ignoring tuned table {path}: schema_version "
            f"{table.get('schema_version')!r} != {SCHEMA_VERSION} "
            "(re-run python -m repro.kernels.tune)")
        return None
    return table


def load_table(backend: Optional[str] = None) -> Optional[Dict[str, Any]]:
    """The tuned table for a backend, or None (missing/corrupt/stale --
    every caller must then fall back to the kernel defaults)."""
    backend = backend or backend_kind()
    return _load_table_cached(backend, table_path(backend))


def invalidate_table_cache() -> None:
    """Forget loaded tables (tests point REPRO_TUNED_DIR elsewhere).
    Already-traced jit executables keep their resolved tiles; use fresh
    ``TuneConfig`` values (a different static arg) to re-resolve."""
    _load_table_cached.cache_clear()


def _cell(tune: TuneConfig, key: str) -> Optional[Dict[str, Any]]:
    if not tune.use_table:
        return None
    table = load_table(tune.backend)
    if not table:
        return None
    return table.get("cells", {}).get(key)


def gram_blocks(m: int, n: int, tune: Optional[TuneConfig] = None,
                bn: Optional[int] = None,
                bk: Optional[int] = None) -> Tuple[int, int]:
    """Resolve the gram (bn, bk) for an (m, n) operand.

    Precedence: explicit kwarg > TuneConfig field > table cell >
    kernel default.  Pure host logic -- safe at trace time."""
    from repro.kernels.gram import gram as _k
    t = tune or DEFAULT
    bn = bn if bn is not None else t.gram_bn
    bk = bk if bk is not None else t.gram_bk
    if bn is None or bk is None:
        cell = _cell(t, gram_key(m, n))
        if cell:
            bn = bn if bn is not None else cell.get("bn")
            bk = bk if bk is not None else cell.get("bk")
    return (bn if bn is not None else _k.DEFAULT_BN,
            bk if bk is not None else _k.DEFAULT_BK)


def qent_tile(n: int, bins: int, tune: Optional[TuneConfig] = None,
              tile: Optional[int] = None) -> int:
    """Resolve the qent tile for an n-element slice at ``bins`` bins
    (same precedence as :func:`gram_blocks`)."""
    from repro.kernels.qent import qent as _k
    t = tune or DEFAULT
    if tile is None:
        tile = t.qent_tile
    if tile is None:
        cell = _cell(t, qent_key(n, bins))
        if cell:
            tile = cell.get("tile")
    return tile if tile is not None else _k.DEFAULT_TILE


# ---------------------------------------------------------------------------
# Timing + search


def time_fn(fn, *args, warmup: int = 1, iters: int = 5, **kwargs) -> float:
    """Compile-excluded median-of-N wall time of ``fn(*args)`` in
    seconds.  Warmup calls absorb tracing/compilation; every timed call
    is fenced with ``jax.block_until_ready`` so async dispatch cannot
    leak work out of the measured window.  The canonical timing helper:
    ``benchmarks/common.time_fn`` re-exports this one."""
    import jax
    for _ in range(max(1, warmup)):
        jax.block_until_ready(fn(*args, **kwargs))
    ts = []
    for _ in range(max(1, iters)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args, **kwargs))
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


# a candidate must beat the default median by >2% to enter the table
HYSTERESIS = 0.02

GRAM_BN_CANDIDATES = (64, 128, 256, 512)
GRAM_BK_CANDIDATES = (64, 128, 256)   # != 128 fails the bit filter (f32
                                      # accumulation order); kept in the
                                      # search to prove the filter works
QENT_TILE_CANDIDATES = (512, 1024, 2048, 4096, 8192)


def _rng(shape, seed: int = 0):
    import numpy as np
    return np.asarray(
        np.random.default_rng(seed).standard_normal(shape), np.float32)


def search_gram_cell(k: int, m: int, n: int, *, warmup: int = 1,
                     iters: int = 5) -> Dict[str, Any]:
    """Tune one gram cell: batched X^T X on a (k, m, n) stack."""
    import numpy as np
    from repro.kernels.gram import gram as _k
    from repro.kernels.gram import ops as gram_ops

    x = _rng((k, m, n))
    default = (_k.DEFAULT_BN, _k.DEFAULT_BK)

    def run(bn, bk):
        return gram_ops.gram_batched(x, bn=bn, bk=bk)

    ref = np.asarray(run(*default))
    t_def = time_fn(run, *default, warmup=warmup, iters=iters)
    best, t_best, discarded = default, t_def, []
    for bn in GRAM_BN_CANDIDATES:
        for bk in GRAM_BK_CANDIDATES:
            if (bn, bk) == default or bn > _bucket_p2(n) * 2 \
                    or bk > _bucket_p2(m) * 2:
                continue
            if not np.array_equal(np.asarray(run(bn, bk)), ref):
                discarded.append([bn, bk])   # bit-safety filter
                continue
            t = time_fn(run, bn, bk, warmup=warmup, iters=iters)
            if t < t_best:
                best, t_best = (bn, bk), t
    if best != default and t_best > t_def * (1 - HYSTERESIS):
        best, t_best = default, t_def
    return {"bn": best[0], "bk": best[1], "t_default": t_def,
            "t_tuned": t_best, "speedup": t_def / t_best,
            "shape": [k, m, n], "discarded_bit_unsafe": discarded}


def search_qent_cell(k: int, n: int, bins: int, e: int, *, warmup: int = 1,
                     iters: int = 5) -> Dict[str, Any]:
    """Tune one qent cell: fused sweep on a (k, n) stack x (e,) bounds."""
    import numpy as np
    from repro.kernels.qent import qent as _k
    from repro.kernels.qent import ops as qent_ops

    x = _rng((k, n), seed=1)
    epss = np.geomspace(1e-3, 1e-1, e).astype(np.float32)

    def run(tile):
        return qent_ops.quantized_entropy_sweep(x, epss, bins, tile=tile)

    t_def = time_fn(run, _k.DEFAULT_TILE, warmup=warmup, iters=iters)
    ref = np.asarray(run(_k.DEFAULT_TILE))
    best, t_best, discarded = _k.DEFAULT_TILE, t_def, []
    for tile in QENT_TILE_CANDIDATES:
        if tile == _k.DEFAULT_TILE or tile % 8 or tile > _bucket_p2(n):
            continue
        if not np.array_equal(np.asarray(run(tile)), ref):
            discarded.append(tile)           # bit-safety filter
            continue
        t = time_fn(run, tile, warmup=warmup, iters=iters)
        if t < t_best:
            best, t_best = tile, t
    if best != _k.DEFAULT_TILE and t_best > t_def * (1 - HYSTERESIS):
        best, t_best = _k.DEFAULT_TILE, t_def
    return {"tile": best, "t_default": t_def, "t_tuned": t_best,
            "speedup": t_def / t_best, "shape": [k, n, bins, e],
            "discarded_bit_unsafe": discarded}


# (k, m, n) gram cells / (k, n, bins, e) qent cells: the shard-size
# buckets the service's row buckets + the paper's 128/256-edge slices
# actually produce.
FULL_GRAM_CELLS = ((8, 128, 128), (4, 256, 256), (2, 512, 512))
FULL_QENT_CELLS = ((2, 16384, 4096, 4), (4, 32768, 4096, 4))
SMOKE_GRAM_CELLS = ((2, 128, 128),)
SMOKE_QENT_CELLS = ((2, 8192, 512, 2),)


def run_search(*, smoke: bool = False, warmup: int = 1,
               iters: int = 5) -> Dict[str, Any]:
    cells: Dict[str, Any] = {}
    for k, m, n in (SMOKE_GRAM_CELLS if smoke else FULL_GRAM_CELLS):
        cells[gram_key(m, n)] = search_gram_cell(
            k, m, n, warmup=warmup, iters=iters)
    for k, n, bins, e in (SMOKE_QENT_CELLS if smoke else FULL_QENT_CELLS):
        cells[qent_key(n, bins)] = search_qent_cell(
            k, n, bins, e, warmup=warmup, iters=iters)
    return {"schema_version": SCHEMA_VERSION, "backend": backend_kind(),
            "cells": cells}


def save_table(table: Dict[str, Any], path: str) -> None:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump(table, f, indent=1, sort_keys=True)
        f.write("\n")
    invalidate_table_cache()


def check_table(backend: Optional[str] = None) -> str:
    """Staleness gate for CI: the table must exist and parse at the
    current schema version (load_table returns None otherwise)."""
    backend = backend or backend_kind()
    invalidate_table_cache()
    if load_table(backend) is None:
        raise SystemExit(
            f"tuned table {table_path(backend)} is missing or stale "
            f"(schema_version != {SCHEMA_VERSION}); regenerate with "
            "python -m repro.kernels.tune")
    return f"tuned table {table_path(backend)} OK (schema {SCHEMA_VERSION})"


def main(argv=None) -> None:
    import argparse
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--backend", default=None,
                   help="table key (default: autodetect)")
    p.add_argument("--smoke", action="store_true",
                   help="2-cell interpret-mode grid (CI perf-lane)")
    p.add_argument("--out", default=None,
                   help="output path (default: kernels/tuned/<backend>.json)")
    p.add_argument("--check", action="store_true",
                   help="staleness gate: verify the existing table only")
    p.add_argument("--iters", type=int, default=5)
    p.add_argument("--warmup", type=int, default=1)
    args = p.parse_args(argv)
    if args.check:
        print(check_table(args.backend))
        return
    table = run_search(smoke=args.smoke, warmup=args.warmup,
                       iters=args.iters)
    if args.backend:
        table["backend"] = args.backend
    out = args.out or table_path(table["backend"])
    save_table(table, out)
    for key, cell in table["cells"].items():
        print(f"{key:28s} -> {cell}")
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
