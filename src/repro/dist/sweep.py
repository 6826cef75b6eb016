"""Multi-device sharded featurization sweeps (the distributed sweep layer).

Production fields don't fit one device and sweep requests arrive
concurrently, so the batched sweep engine
(``repro.core.predictors.features_sweep``) gains a ``shard_map`` path over
its slice axis here: the (k, m, n) slice stack -- or (k, d, m, n) volume
stack, sharded identically over k -- is split across the mesh axis the
logical ``"slices"`` axis maps to (``"data"`` under the default rules of
``repro.dist.sharding``), each device runs the fused single-device sweep
body on its local shard -- one batched Gram + eigvalsh per 2-D stack (one
per HOSVD mode for volumes) and one multi-eps q-ent pass per shard, grid
dim 0 of both batched kernels -- and the per-device ``(k_local, e, 2)``
results are reassembled into the global ``(k, e, 2)`` tensor.

Slice counts that don't divide the mesh extent are padded with copies of
the last slice; the pad rows are dropped from the gathered result
(``gather=True``) or zero-masked in the still-sharded padded result
(``gather=False``, for pipelines whose downstream stages stay
distributed).

Typical invocation on a multi-device CPU host (the flag must be exported
before jax is imported)::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 python ...

    from repro.dist import sharding as S
    from repro.launch import mesh as M
    with S.use_mesh(M.make_sweep_mesh()):
        feats = predictors.features_sweep(slices, ebs)   # auto-sharded

Multi-process fabric
--------------------
The same entry points accept PROCESS-SPANNING meshes: after
``repro.launch.mesh.dist_init(...)`` on every process,
``make_sweep_mesh()`` covers ``jax.process_count() x
local_device_count`` devices and the sweep runs as one collective
launch.  Two ingestion contracts:

* **SPMD (default)** -- every process passes the identical global
  (k, ...) stack; each process uploads only its contiguous block of
  rows to its own devices (``jax.make_array_from_process_local_data``),
  so no process ever materializes the stack on-device.
* **process-local** (``process_local=True, global_k=``) -- each process
  passes ONLY the rows :func:`process_block` assigns it (scale-out
  ingestion: each host reads its own rows from disk/network).

Padding generalizes across processes: the global stack is padded to a
multiple of the mesh extent and real row *i* always lives at global
position *i*, so the pad rows occupy the trailing positions -- they
live on the LAST process -- and ``gather=True`` drops them /
``gather=False`` masks them exactly like the single-process path.  The
gather is a ``multihost_utils.process_allgather``, so every process
returns the full (k, e, 2) tensor.

Training support: ``training_crs`` partitions the *compressor* runs an
``EbGridModel`` fit needs over the processes of the SAME sweep mesh
(each host compresses only its contiguous block of slices) and
all-gathers the (k, e) CR table, matching the sweep's
features-all-gathered / CRs-computed-locally cost structure.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.dist import sharding as S


def active_sweep_mesh(mesh: Optional[Mesh] = None) -> Optional[Mesh]:
    """The mesh a sweep should shard over, or None for single-device.

    Returns ``mesh`` (or the active ``use_mesh`` mesh) when the logical
    "slices" axis resolves to a physical extent > 1 and we are not already
    inside a manual shard_map body (where the engine must run locally).
    """
    mesh = mesh if mesh is not None else S.current_mesh()
    if mesh is None or S.in_manual_context():
        return None
    axes = S._physical_axes("slices", mesh)
    if S._mesh_extent(mesh, axes) <= 1:
        return None
    return mesh


def slice_axes(mesh: Mesh) -> tuple:
    """Physical mesh axes the slice axis shards over (non-empty tuple)."""
    axes = S._physical_axes("slices", mesh)
    if not axes:
        raise ValueError(
            f"mesh {mesh.axis_names} has no axis for the logical 'slices' "
            "axis; add a rules entry mapping 'slices' to a mesh axis")
    return axes


# ---------------------------------------------------------------------------
# Multi-process fabric helpers
# ---------------------------------------------------------------------------

def mesh_spans_processes(mesh: Optional[Mesh]) -> bool:
    """True when ``mesh`` places devices on more than one process."""
    if mesh is None:
        return False
    return len({d.process_index for d in mesh.devices.flat}) > 1


def mesh_processes(mesh: Mesh) -> list[int]:
    """Sorted process indices participating in ``mesh``."""
    return sorted({d.process_index for d in mesh.devices.flat})


def _process_position(mesh: Mesh) -> tuple[int, int]:
    """(position of this process among the mesh's processes, #processes).

    Raises when the calling process owns none of the mesh's devices --
    such a process cannot join the collective launch and silently
    continuing would hang the others.
    """
    procs = mesh_processes(mesh)
    me = jax.process_index()
    if me not in procs:
        raise ValueError(
            f"process {me} has no devices in mesh {mesh.axis_names} "
            f"(processes {procs}); every participating process must build "
            "the mesh over devices it contributes")
    return procs.index(me), len(procs)


def _device_spans(mesh: Mesh) -> dict:
    """{process index: (first flat device position, device count)} for
    ``mesh``, requiring each process's devices to be CONTIGUOUS in mesh
    order (true for ``make_sweep_mesh``: ``jax.devices()`` is
    process-ordered) so per-process row blocks are contiguous too."""
    spans: dict = {}
    for i, d in enumerate(mesh.devices.flat):
        p = d.process_index
        if p not in spans:
            spans[p] = (i, 1)
        else:
            first, n = spans[p]
            if first + n != i:
                raise ValueError(
                    f"mesh {mesh.axis_names} interleaves process {p}'s "
                    "devices with other processes'; the sweep fabric "
                    "needs contiguous per-process device blocks (build "
                    "the mesh with launch.mesh.make_sweep_mesh)")
            spans[p] = (first, n + 1)
    return spans


def process_block(k: int, mesh: Mesh) -> tuple[int, int]:
    """[lo, hi) rows of a k-row global stack THIS process ingests.

    The padded global row count ``k_pad = ceil(k / extent) * extent``
    distributes ``k_pad / extent`` rows per device, so each process's
    contiguous block is proportional to the devices it contributes
    (processes may own UNEQUAL device counts, e.g. a mesh built over a
    prefix of the global device list); blocks are clipped to the real
    ``k``, which keeps real row *i* at global position *i* and pushes
    every pad row to the trailing positions -- the pad lives on the
    last process(es).
    """
    axes = slice_axes(mesh)
    ext = S._mesh_extent(mesh, axes)
    _process_position(mesh)          # membership check (clear error)
    first, ndev = _device_spans(mesh)[jax.process_index()]
    k_pad = -(-k // ext) * ext
    rpd = k_pad // ext               # rows per device
    return min(first * rpd, k), min((first + ndev) * rpd, k)


def gather_rows(out) -> np.ndarray:
    """Bring a (possibly process-spanning) sweep result to the host.

    Fully-addressable arrays transfer directly; global arrays with
    non-addressable shards are collectively all-gathered first (every
    participating process must call this -- it is the sweep fabric's one
    synchronization point).  The all-gather is RUNTIME-global
    (``multihost_utils.process_allgather``), so it requires every
    process of the ``jax.distributed`` runtime to be alive; on a
    recovered fabric whose mesh no longer spans every runtime process
    use :func:`replicate_rows` instead.
    """
    if isinstance(out, jax.Array) and not out.is_fully_addressable:
        from jax.experimental import multihost_utils
        return np.asarray(multihost_utils.process_allgather(out, tiled=True))
    return np.asarray(out)


def replicate_rows(out, mesh: Mesh) -> np.ndarray:
    """MESH-scoped gather: replicate ``out`` across ``mesh`` and read
    the local copy.

    Equivalent in value to :func:`gather_rows` but the collective is
    scoped to ``mesh``'s processes only (a jitted identity with
    replicated out_shardings), so it works on a shrunken survivor
    submesh while dead runtime peers would wedge/abort the
    runtime-global ``process_allgather``.  Every process owning devices
    in ``mesh`` must make this call.
    """
    if not isinstance(out, jax.Array) or out.is_fully_addressable:
        return np.asarray(out)
    sh = NamedSharding(mesh, P(*([None] * out.ndim)))
    rep = jax.jit(lambda a: a, out_shardings=sh)(out)
    return np.asarray(rep.addressable_shards[0].data)


def invalidate_mesh_caches() -> None:
    """Drop every cached sharded-sweep executable.

    Called after elastic recovery rebuilds the mesh: executables
    compiled for the OLD mesh are keyed by it and would never be hit
    again, but they pin compiled programs (and device references) that
    include lost processes -- clear the lot so the shrunken fabric
    recompiles only what it uses.
    """
    _sharded_sweep_fn.cache_clear()


def _global_stack(local: np.ndarray, global_shape: tuple, mesh: Mesh,
                  axes: tuple):
    """Assemble the global padded (k_pad, ...) device array from this
    process's padded block (``jax.make_array_from_process_local_data``:
    each process uploads only its own rows)."""
    part = axes[0] if len(axes) == 1 else axes
    spec = P(part, *([None] * (len(global_shape) - 1)))
    return jax.make_array_from_process_local_data(
        NamedSharding(mesh, spec), local, global_shape)


def _replicated(x: np.ndarray, mesh: Mesh):
    """A globally-replicated device array from identical per-process
    host values (error-bound vectors, masks)."""
    sh = NamedSharding(mesh, P(*([None] * x.ndim)))
    return jax.make_array_from_process_local_data(sh, x, x.shape)


def _pad_block(block: np.ndarray, per: int, shape_tail: tuple,
               dtype) -> np.ndarray:
    """Pad a process's local row block to its ``per``-row device block.

    Pad rows repeat the block's last real row (keeps the eigensolve and
    the q-ent sort numerically unexceptional); a process with NO real
    rows (k far below the mesh extent) feeds zeros -- pad rows are
    dropped or masked downstream, so their values never surface.
    """
    n = block.shape[0]
    if n == per:
        return np.ascontiguousarray(block)
    if n == 0:
        return np.zeros((per,) + shape_tail, dtype)
    return np.concatenate(
        [block, np.broadcast_to(block[-1:], (per - n,) + shape_tail)], axis=0)


def _features_sweep_multihost(slices, epss, cfg, mesh: Mesh, gather: bool,
                              process_local: bool, global_k: Optional[int],
                              donate: bool = False, mode: str = "features"):
    """Process-spanning sweep launch (see module docstring): per-process
    ingestion -> one collective shard_map -> ``process_allgather``."""
    from repro.core import predictors as PRED
    axes = slice_axes(mesh)
    ext = S._mesh_extent(mesh, axes)
    _process_position(mesh)          # membership check (clear error)
    host = np.asarray(slices)

    if process_local:
        if global_k is None:
            raise ValueError(
                "process_local=True needs global_k= (the total row count "
                "across processes; each process passes only the rows "
                "process_block(global_k, mesh) assigns it)")
        k = int(global_k)
        lo, hi = process_block(k, mesh)
        if host.shape[0] != hi - lo:
            raise ValueError(
                f"process {jax.process_index()} must ingest rows "
                f"[{lo}, {hi}) of the {k}-row global stack, got "
                f"{host.shape[0]} rows (use process_block to split)")
        local = host
    else:
        k = host.shape[0]
        lo, hi = process_block(k, mesh)
        local = host[lo:hi]

    k_pad = -(-k // ext) * ext
    # this process's device block is proportional to the devices it
    # contributes (per-process shares may be unequal)
    _, ndev = _device_spans(mesh)[jax.process_index()]
    per = (k_pad // ext) * ndev
    local = _pad_block(local, per, host.shape[1:], host.dtype)
    garr = _global_stack(local, (k_pad,) + host.shape[1:], mesh, axes)
    eps_np = np.asarray(epss, np.float32).reshape(-1)
    eps_g = _replicated(eps_np, mesh)

    out = _sharded_sweep_fn(
        mesh, axes, host.ndim,
        PRED.variance_fraction_for(cfg, host.ndim), cfg.qent_bins,
        cfg.use_kernels, cfg.tune,
        # garr is assembled fresh from host memory every launch, so
        # donating it back to XLA is always safe here
        donate, mode)(garr, eps_g)

    if gather:
        return jnp.asarray(gather_rows(out)[:k])
    if k_pad > k:                                       # mask pad rows
        mask = (np.arange(k_pad) < k).astype(np.float32).reshape(-1, 1, 1)
        out = out * _replicated(mask, mesh)
    return out


@functools.lru_cache(maxsize=32)
def _sharded_sweep_fn(mesh: Mesh, axes: tuple, rank: int, vf: float,
                      bins: int, use_kernels: bool, tune=None,
                      donate: bool = False, mode: str = "features"):
    """jit'd shard_map sweep for one (mesh, stack rank, config); cached so
    repeated sweeps (serving, training grids) reuse the compiled
    executable.  ``rank`` is the stack's ndim: 3 for (k, m, n) slice
    stacks, 4 for (k, d, m, n) volume stacks -- only dim 0 is sharded
    either way.  ``donate=True`` compiles a variant that donates the
    input stack's buffer (identical math; serving hot path).  ``mode``
    selects the emitted tensor ("features" | "quality" | "both", see
    ``predictors.SWEEP_MODE_WIDTHS``) -- the output stays rank-3 at
    every width, so the specs below are mode-agnostic."""
    from repro.core import predictors as PRED

    part = axes[0] if len(axes) == 1 else axes

    def body(local_slices, epss):
        # each device featurizes its (k_local, ...) shard with the exact
        # single-device sweep body: sharded == single-device to f32 tol
        return PRED._features_sweep_impl(
            local_slices, epss, vf=vf, bins=bins, use_kernels=use_kernels,
            tune=tune, mode=mode)

    # check_vma=False: the body is per-device local compute with no
    # collectives, and its Pallas kernels declare no varying-axes types
    f = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(part, *([None] * (rank - 1))), P(None)),
        out_specs=P(part, None, None),
        axis_names=frozenset(axes), check_vma=False)
    return jax.jit(f, donate_argnums=(0,) if donate else ())


def features_sweep_sharded(
    slices: jnp.ndarray,
    epss,
    cfg=None,
    *,
    mesh: Optional[Mesh] = None,
    gather: bool = True,
    process_local: bool = False,
    global_k: Optional[int] = None,
    donate: bool = False,
    mode: str = "features",
) -> jnp.ndarray:
    """``features_sweep`` sharded over the slice axis of ``mesh``.

    (k, m, n) or (k, d, m, n) x (e,) -> (k, e, 2) [``gather=True``] or the
    padded (k_pad, e, 2) result still sharded over the mesh with pad rows
    zeroed [``gather=False``]; ``k_pad = ceil(k / extent) * extent``.
    Volume stacks shard the k axis exactly like slice stacks do (each
    device runs the batched HOSVD + q-ent body on its local shard).

    Process-spanning meshes run the collective multihost path (module
    docstring): every participating process must make this call with the
    same shapes.  ``process_local=True`` (with ``global_k=``) switches
    the ingestion contract from "identical global stack on every
    process" to "each process passes only its :func:`process_block`
    rows"; with ``gather=True`` every process still returns the full
    (k, e, 2) tensor (``process_allgather``).

    Falls back to the single-device engine when no mesh (or an extent-1
    mesh) is available, so callers can route unconditionally.

    ``donate=True`` donates the input stack's device buffer to the
    launch (zero-copy serving hot path).  The result is bit-identical;
    the caller's ``slices`` array is consumed and must not be reused
    (numpy inputs are unaffected -- only their fresh device upload is
    donated).

    ``mode`` selects the emitted tensor exactly as in
    ``predictors._features_sweep_impl`` ("features" | "quality" |
    "both"); pad-row masking and gathering are width-agnostic.
    """
    from repro.core import predictors as PRED
    cfg = cfg if cfg is not None else PRED.PredictorConfig()
    mesh = active_sweep_mesh(mesh)
    if mesh is None:
        if process_local:
            raise ValueError(
                "process_local=True needs a process-spanning mesh "
                "(dist_init + make_sweep_mesh); no usable mesh is active")
        return PRED._sweep_dispatch(jnp.asarray(slices), epss, cfg,
                                    sharded=False, mesh=None, gather=True,
                                    mode=mode)
    if slices.ndim not in (3, 4):
        raise ValueError(
            f"features_sweep_sharded expects (k, m, n) or (k, d, m, n), "
            f"got {slices.shape}")
    PRED._validate_eps_positive(epss)
    if mesh_spans_processes(mesh):
        return _features_sweep_multihost(
            slices, epss, cfg, mesh, gather, process_local, global_k, donate,
            mode)
    if process_local:
        raise ValueError(
            "process_local=True is only meaningful on a process-spanning "
            f"mesh; mesh {mesh.axis_names} lives on one process")
    epss = jnp.asarray(epss, jnp.float32).reshape(-1)

    axes = slice_axes(mesh)
    ext = S._mesh_extent(mesh, axes)
    k = slices.shape[0]
    pad = (-k) % ext
    if pad:
        # pad with the last slice (real data: keeps the eigensolve and the
        # q-ent sort on the padded rows numerically unexceptional); the
        # concat result is owned here, so its buffer is donatable
        slices = jnp.concatenate(
            [slices, jnp.broadcast_to(slices[-1:], (pad,) + slices.shape[1:])],
            axis=0)
        donate = True

    out = _sharded_sweep_fn(
        mesh, axes, slices.ndim,
        PRED.variance_fraction_for(cfg, slices.ndim), cfg.qent_bins,
        cfg.use_kernels, cfg.tune, donate, mode)(slices, epss)

    if gather:
        out = out[:k]                                   # drop pad rows
        return jax.device_put(
            out, NamedSharding(mesh, P(None, None, None)))
    if pad:                                             # mask pad rows
        mask = (jnp.arange(k + pad) < k).astype(out.dtype)
        out = out * mask[:, None, None]
    return out


# ---------------------------------------------------------------------------
# Serve-side coalescing: padded bucketed launches + per-request scatter-back
# ---------------------------------------------------------------------------

def sweep_padded(
    slices: jnp.ndarray,
    epss,
    cfg=None,
    *,
    k_pad: Optional[int] = None,
    mesh: Optional[Mesh] = None,
    donate: bool = False,
    mode: str = "features",
) -> jnp.ndarray:
    """One coalesced sweep launch over a padded request batch.

    The sweep service stacks several requests' slices (or volumes: any
    shared trailing shape) into one (k, m, n) / (k, d, m, n) batch, pads
    it to a *bucketed* ``k_pad`` (so a small set of compiled
    executables serves every batch size), and launches once:

    * ``k_pad`` a multiple of the mesh's slice extent -> the ``shard_map``
      path with ``gather=False`` (each device keeps its shard; no
      reshard/gather between launch and scatter-back, and the bucket pad
      doubles as the mesh pad so no second padding happens inside);
    * otherwise (no mesh, or a bucket below the extent) -> the
      single-device fused engine.

    Process-spanning meshes launch collectively: every participating
    process calls ``sweep_padded`` with the same (stack, epss, k_pad)
    -- the sweep service's leader/follower mode broadcasts exactly these
    -- and the returned global array's shards stay on their processes
    until ``gather_rows``/``scatter_requests`` all-gathers them.  A
    bucket below the global extent drops every process to the identical
    local computation, so the branch stays deadlock-free.

    Returns the PADDED (k_pad, e, 2) result; rows past the true batch are
    garbage-by-construction (copies of the last slice) and the caller
    scatters only real rows back to requests (``scatter_requests``).
    Every kept row is bit-identical to a single-request launch of that
    slice because the sweep body is row-independent.

    ``donate=True`` donates the stack's device buffer to the launch (the
    sweep service always passes it: the packed batch is service-owned
    staging memory).  When padding happens here the padded copy is owned
    and donated regardless.  Donation never changes the result -- only
    buffer lifetime -- and donated launches are asserted bit-equal to
    non-donated ones in tests/test_tune.py.

    ``mode`` selects the emitted tensor ("features" | "quality" |
    "both") -- the quality launcher in ``serve/method.py`` rides this
    exact entry point with ``mode="quality"``.
    """
    from repro.core import predictors as PRED
    cfg = cfg if cfg is not None else PRED.PredictorConfig()
    if slices.ndim not in (3, 4):
        raise ValueError(
            f"sweep_padded expects (k, m, n) or (k, d, m, n), "
            f"got {slices.shape}")
    PRED._validate_eps_positive(epss)
    k = slices.shape[0]
    k_pad = k if k_pad is None else int(k_pad)
    if k_pad < k:
        raise ValueError(f"k_pad={k_pad} smaller than batch k={k}")
    if k_pad > k:
        slices = jnp.concatenate(
            [slices,
             jnp.broadcast_to(slices[-1:], (k_pad - k,) + slices.shape[1:])],
            axis=0)
        donate = True            # the padded copy is owned here
    epss = jnp.asarray(epss, jnp.float32).reshape(-1)
    mesh = active_sweep_mesh(mesh)
    if mesh is not None:
        ext = S._mesh_extent(mesh, slice_axes(mesh))
        if k_pad >= ext and k_pad % ext == 0:
            return features_sweep_sharded(
                slices, epss, cfg, mesh=mesh, gather=False, donate=donate,
                mode=mode)
    fn = (PRED._features_sweep_donated if donate
          else PRED._features_sweep_traced)
    return fn(
        slices, epss, vf=PRED.variance_fraction_for(cfg, slices.ndim),
        bins=cfg.qent_bins, use_kernels=cfg.use_kernels, tune=cfg.tune,
        mode=mode)


def scatter_requests(out, sizes: Sequence[int]) -> list:
    """Scatter a coalesced (k_pad, e, 2) sweep result back into
    per-request row blocks.

    ONE host transfer for the whole batch (for the ``gather=False``
    sharded layout this is the only gather point; process-spanning
    results are collectively all-gathered, so every participating
    process must reach this call); ``sizes`` are the per-request row
    counts in stacking order, and trailing pad rows are dropped.
    Returns a list of (sizes[i], e, 2) numpy arrays.
    """
    host = gather_rows(out)
    total = int(np.sum(sizes)) if len(sizes) else 0
    if total > host.shape[0]:
        raise ValueError(
            f"request sizes sum to {total} but the result has only "
            f"{host.shape[0]} rows")
    blocks, off = [], 0
    for s in sizes:
        blocks.append(host[off:off + s])
        off += s
    return blocks


# ---------------------------------------------------------------------------
# Training-side distribution: compressor runs over local slice shards
# ---------------------------------------------------------------------------

def _even_bounds(k: int, parts: int, index: int) -> tuple[int, int]:
    """Contiguous [lo, hi) block of ``k`` items for shard ``index`` of
    ``parts`` (remainder spread over the leading shards)."""
    base, rem = divmod(k, parts)
    lo = index * base + min(index, rem)
    return lo, lo + base + (1 if index < rem else 0)


def training_crs(comp, slices, ebs: Sequence[float], *,
                 mesh: Optional[Mesh] = None) -> np.ndarray:
    """The (k, e) compression-ratio table an ``EbGridModel`` fit needs,
    with the compressor executions partitioned over processes.

    Each process runs the (host-side, numpy) compressor only on its
    contiguous block of slices and the table is all-gathered, so the
    expensive training-time compressor runs scale out with hosts exactly
    like the featurization sweep scales out with devices.  The partition
    is MESH-driven: pass the same process-spanning sweep mesh the
    featurization sharded over and the compressor runs split across that
    mesh's processes (every one of them must make this call -- the
    gather is collective).  Without a process-spanning mesh this is the
    plain full local loop, so single-process callers (tests, CI, a
    service leader training models on the side) never block on a
    collective.
    """
    k = len(slices)
    if mesh_spans_processes(mesh):
        index, parts = _process_position(mesh)
    else:
        parts, index = 1, 0
    lo, hi = _even_bounds(k, parts, index)
    table = np.zeros((k, len(ebs)), np.float64)
    for i in range(lo, hi):
        for j, eps in enumerate(ebs):
            table[i, j] = float(comp.cr(slices[i], float(eps)))
    if parts == 1:
        return table
    from jax.experimental import multihost_utils
    # non-local rows are zero, so summing the per-process tables
    # reconstructs the full (k, e) table.  The gather moves the raw f64
    # BYTES (uint8 payload): jnp would silently downcast float64 to f32
    # under the default x64-disabled config, and training tables must be
    # identical to the serial loop.
    payload = np.frombuffer(table.tobytes(), np.uint8)
    stacked = np.asarray(multihost_utils.process_allgather(payload))
    return sum(np.frombuffer(stacked[p].tobytes(), np.float64)
               .reshape(table.shape) for p in range(stacked.shape[0]))
