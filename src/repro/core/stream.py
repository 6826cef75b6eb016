"""Incremental streaming sweep driver: fixed-budget chunks in, the full
per-variable feature tensor out.

``features_sweep`` (and everything stacked on it) takes a resident
``(k, ...)`` array; this module drives the SAME sweep body over a
:class:`repro.data.source.DatasetSource` variable chunk by chunk, so a
variable far larger than device (or host) memory featurizes with a
bounded footprint:

* **Chunking** -- ``rows_per_chunk`` sizes every chunk to a byte budget;
  all chunks launch padded to one fixed row bucket (the full-chunk row
  count), so the whole stream compiles ONE executable and the ragged
  final chunk reuses it.
* **Double buffering** -- a reader thread stages chunk ``n+1`` (file
  read + f64->f32 conversion + optional running content digest) behind a
  bounded queue while chunk ``n``'s launch executes; launches are
  dispatched asynchronously and drained ``max_in_flight`` behind, so
  host I/O overlaps device compute (``prefetch=0`` degrades to the
  strictly synchronous read -> launch -> block loop, which is the
  baseline ``bench_stream`` gates against).
* **Zero-copy ingestion** -- every chunk is a fresh service-owned f32
  staging copy, so its device upload is donated
  (``dist.sweep.sweep_padded(donate=True)``, PR 8's contract).
* **Incremental aggregation** -- per-chunk ``(k_chunk, e, 2)`` blocks
  concatenate into the full ``(k, e, 2)`` tensor.  The sweep body is
  row-independent (the serving layer's coalescing contract), so the
  streamed tensor is BIT-EQUAL to one in-memory ``features_sweep``
  launch; tests and ``bench_stream`` assert it.
* **Multi-process streaming** -- under a process-spanning mesh each
  process reads ONLY its ``dist.sweep.process_block`` rows of every
  chunk and the chunk launches collectively via the PR 5
  ``process_local`` ingestion contract (same chunk schedule everywhere:
  boundaries depend only on the row count and the budget).
"""
from __future__ import annotations

import dataclasses
import queue
import threading
from collections import deque
from typing import Dict, Optional

import numpy as np

from repro import obs
from repro.core import predictors as PRED
from repro.data.source import DatasetSource, StreamingDigest, rows_per_chunk
from repro.dist import sweep as DS


@dataclasses.dataclass(frozen=True)
class StreamConfig:
    """Knobs of the incremental driver.

    ``budget_bytes`` caps one chunk's f32 bytes (the peak host staging
    AND device upload per launch -- set it at or below the device memory
    budget).  ``prefetch`` is how many chunks the reader thread stages
    ahead (0 = fully synchronous, no reader thread).  ``max_in_flight``
    bounds dispatched-but-undrained launches so device memory holds at
    most that many chunk uploads."""
    budget_bytes: int = 64 << 20
    prefetch: int = 2
    max_in_flight: int = 2

    def __post_init__(self):
        if self.budget_bytes <= 0:
            raise ValueError(
                f"budget_bytes must be positive, got {self.budget_bytes}")
        if self.prefetch < 0 or self.max_in_flight < 1:
            raise ValueError(
                f"prefetch must be >= 0 and max_in_flight >= 1, got "
                f"prefetch={self.prefetch} max_in_flight={self.max_in_flight}")


_DONE = object()


def _read(source: DatasetSource, name: str, lo: int, hi: int,
          digest: Optional[StreamingDigest]) -> np.ndarray:
    """Stage rows [lo, hi): read + f32 convert + digest."""
    with obs.span("repro.stream.read", rows=hi - lo) as sp:
        arr = source.read_rows(name, lo, hi)
        if digest is not None:
            digest.update(arr)
        sp.attrs["bytes"] = arr.nbytes
    return arr


def _reader(source: DatasetSource, name: str, schedule, q: "queue.Queue",
            digest: Optional[StreamingDigest]) -> None:
    """Reader-thread body: stage chunks (read + f32 convert + digest)
    into the bounded queue; exceptions travel through the queue so the
    consumer re-raises them instead of hanging."""
    try:
        for lo, hi, rlo, rhi in schedule:
            q.put((lo, hi, _read(source, name, rlo, rhi, digest)))
        q.put(_DONE)
    except BaseException as exc:             # noqa: BLE001 -- re-raised
        q.put(exc)


def _staged_chunks(source, name, schedule, prefetch: int,
                   digest: Optional[StreamingDigest]):
    """Iterate ``(lo, hi, rows)`` chunks: behind a ``prefetch``-bounded
    reader thread, or inline when ``prefetch == 0`` (synchronous)."""
    if prefetch <= 0:
        for lo, hi, rlo, rhi in schedule:
            yield lo, hi, _read(source, name, rlo, rhi, digest)
        return
    q: "queue.Queue" = queue.Queue(maxsize=prefetch)
    t = threading.Thread(target=_reader,
                         args=(source, name, schedule, q, digest),
                         daemon=True)
    t.start()
    try:
        while True:
            with obs.span("repro.stream.wait"):
                item = q.get()
            if item is _DONE:
                break
            if isinstance(item, BaseException):
                raise item
            yield item
    finally:
        t.join(timeout=5.0)


def chunk_schedule(k: int, chunk: int, mesh=None) -> list:
    """The deterministic chunk plan: ``(lo, hi, read_lo, read_hi)`` per
    chunk.  ``read_*`` is the sub-range THIS process ingests -- the full
    chunk on a single process, the chunk's :func:`dist.sweep.
    process_block` block under a process-spanning mesh.  Boundaries
    depend only on ``(k, chunk)``, so every process of a cohort computes
    the identical schedule."""
    multiproc = DS.mesh_spans_processes(mesh)
    sched = []
    for lo in range(0, k, chunk):
        hi = min(lo + chunk, k)
        if multiproc:
            blo, bhi = DS.process_block(hi - lo, mesh)
            sched.append((lo, hi, lo + blo, lo + bhi))
        else:
            sched.append((lo, hi, lo, hi))
    return sched


def stream_features(
    source: DatasetSource,
    name: str,
    epss,
    cfg: Optional[PRED.PredictorConfig] = None,
    *,
    stream: Optional[StreamConfig] = None,
    mesh=None,
    digest: Optional[StreamingDigest] = None,
    quality: bool = False,
) -> np.ndarray:
    """Featurize one variable of ``source`` chunk by chunk: the full
    ``(k, e, 2)`` tensor, bit-equal to ``features_sweep(source.read(
    name), epss, cfg)``, with at most one ``budget_bytes`` chunk of the
    variable resident at a time.

    ``quality=True`` streams the fused "both" sweep -- each chunk launch
    emits the concatenated (k_chunk, e, 4) features+quality tensor from
    one read -- and returns the pair ``(features (k, e, 2), quality
    (k, e, 2))``, each half bit-equal to its in-memory counterpart
    (``features_sweep`` / ``quality_sweep``).

    ``digest``: a :class:`repro.data.source.StreamingDigest` updated
    with every chunk in row order; after the call ``digest.digest()``
    equals ``serve.method.slice_digest`` of the fully materialized
    variable (the out-of-core FeatureCache key) without the variable
    ever having been resident.  Single-process only: under a
    process-spanning mesh each process reads only its block, so no
    process sees every byte.

    Under a process-spanning mesh (``dist_init`` + a mesh over every
    process's devices) the call is COLLECTIVE: every process streams the
    same schedule, reads only its ``process_block`` rows of each chunk,
    and returns the identical full tensor.
    """
    cfg = cfg if cfg is not None else PRED.PredictorConfig()
    stream = stream if stream is not None else StreamConfig()
    PRED._validate_eps_positive(epss)
    epss_np = np.asarray(epss, np.float32).reshape(-1)
    meta = source.meta(name)
    if len(meta.shape) not in (3, 4):
        raise ValueError(
            f"stream_features expects a (k, m, n) or (k, d, m, n) "
            f"variable, got {name!r} with shape {meta.shape}")
    mode = "both" if quality else "features"
    width = PRED.SWEEP_MODE_WIDTHS[mode]
    k = meta.rows
    if k == 0:
        empty = np.zeros((0, len(epss_np), width), np.float32)
        return (empty[..., :2], empty[..., 2:]) if quality else empty
    mesh = DS.active_sweep_mesh(mesh)
    multiproc = DS.mesh_spans_processes(mesh)
    if multiproc and digest is not None:
        raise ValueError(
            "digest= is single-process only: under a process-spanning "
            "mesh each process reads only its block of every chunk, so "
            "no single process observes the variable's full byte stream")
    chunk = rows_per_chunk(meta, stream.budget_bytes)
    schedule = chunk_schedule(k, chunk, mesh)

    results: list = [None] * len(schedule)
    pending: deque = deque()                 # (index, launch, real_rows)

    def drain_one() -> None:
        idx, out, rows = pending.popleft()
        with obs.span("repro.stream.drain"):
            results[idx] = np.asarray(DS.gather_rows(out)[:rows],
                                      np.float32)

    chunks = _staged_chunks(source, name, schedule,
                            stream.prefetch, digest)
    for idx, (lo, hi, arr) in enumerate(chunks):
        rows = hi - lo
        if multiproc:
            # collective per-chunk launch; gather_rows inside
            # features_sweep_sharded is the synchronization point, so
            # the result is already on the host
            out = DS.features_sweep_sharded(
                arr, epss_np, cfg, mesh=mesh, gather=True,
                process_local=True, global_k=rows, donate=True, mode=mode)
            results[idx] = np.asarray(out, np.float32)
            continue
        # every chunk launches padded to the SAME bucket (the full-chunk
        # row count): one compiled executable serves the whole stream,
        # ragged final chunk included, and the fresh staging copy's
        # upload is donated (zero-copy ingestion)
        with obs.span("repro.stream.launch", rows=rows,
                      rows_launched=chunk):
            out = DS.sweep_padded(arr, epss_np, cfg, k_pad=chunk, mesh=mesh,
                                  donate=True, mode=mode)
        pending.append((idx, out, rows))
        # async dispatch: block only when the in-flight window is full
        # (prefetch=0 keeps the strictly synchronous baseline semantics)
        while pending and (stream.prefetch <= 0
                           or len(pending) > stream.max_in_flight):
            drain_one()
    while pending:
        drain_one()
    full = np.concatenate(results, axis=0)
    if quality:
        return full[..., :2], full[..., 2:]
    return full


def stream_dataset(
    source: DatasetSource,
    epss,
    cfg: Optional[PRED.PredictorConfig] = None,
    *,
    stream: Optional[StreamConfig] = None,
    mesh=None,
    digests: Optional[Dict[str, str]] = None,
) -> Dict[str, np.ndarray]:
    """:func:`stream_features` over every variable of ``source``;
    returns ``{variable: (k, e, 2)}``.  ``digests`` (when given, and on
    a single process) is filled with each variable's streaming content
    digest -- the FeatureCache key of the whole variable."""
    out: Dict[str, np.ndarray] = {}
    multiproc = DS.mesh_spans_processes(DS.active_sweep_mesh(mesh))
    for name in source.variables():
        d = StreamingDigest() if digests is not None and not multiproc \
            else None
        out[name] = stream_features(source, name, epss, cfg, stream=stream,
                                    mesh=mesh, digest=d)
        if d is not None:
            digests[name] = d.digest()
    return out
