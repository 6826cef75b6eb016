"""Compressor-agnostic statistical predictors of lossy compressibility.

Implements the paper's Section 3.1:
  * ``svd_trunc``      -- fraction of singular values needed to recover 99%
                          of the variance of a mean-corrected 2-D slice
                          (proxy for spatial correlation range).
  * ``hosvd_trunc``    -- 3-D extension: Tucker/HOSVD unfolding truncation at
                          90% of squared singular mass per mode.
  * ``std``            -- slice standard deviation.
  * ``entropy``        -- Shannon entropy of the raw symbol distribution.
  * ``quantized_entropy`` -- entropy of ``Q(d, eps) = floor(d/eps)*eps``:
                          the paper's lossyness-aware entropy.

TPU adaptation (DESIGN.md section 4): singular values are obtained from the
eigenvalues of the Gram matrix ``X^T X`` (MXU-friendly matmul + small
symmetric eigensolve) instead of a LAPACK bidiagonalisation; the Gram matmul
has a Pallas kernel in ``repro.kernels.gram``.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Sequence

import jax
import jax.numpy as jnp


from repro.kernels import tune as KT
from repro import f32, quant
from repro.quant import validate_eps_positive as _validate_eps_positive

DEFAULT_VARIANCE_FRACTION_2D = 0.99
DEFAULT_VARIANCE_FRACTION_3D = 0.90

# The Gram matrices are f32 products.  On the TPU a default-precision f32
# matmul rounds its operands to bf16, which moves the truncation count.
_F32 = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# SVD truncation level (2-D)
# ---------------------------------------------------------------------------

def svd_trunc(
    x: jnp.ndarray,
    variance_fraction: float = DEFAULT_VARIANCE_FRACTION_2D,
    use_kernel: bool = False,
    tune: KT.TuneConfig | None = None,
) -> jnp.ndarray:
    """Fraction of singular values needed to capture ``variance_fraction``
    of the total variance of the mean-corrected 2-D slice ``x``.

    Returns a scalar in (0, 1].  Low values => strong spatial correlation.
    The k=1 case of ``svd_trunc_batch`` (single implementation).
    """
    if x.ndim != 2:
        raise ValueError(f"svd_trunc expects a 2-D slice, got shape {x.shape}")
    return svd_trunc_batch(x[None], variance_fraction, use_kernel=use_kernel,
                           tune=tune)[0]


# ---------------------------------------------------------------------------
# HOSVD truncation level (3-D)
# ---------------------------------------------------------------------------

def _unfold_batch(x: jnp.ndarray, mode: int) -> jnp.ndarray:
    """Mode-``mode`` unfolding of every tensor in a (k, ...) stack: fibers
    of (per-tensor) dimension ``mode`` become columns -> (k, dims[mode], -1).
    """
    return jnp.moveaxis(x, 1 + mode, 1).reshape(x.shape[0], x.shape[1 + mode], -1)


def hosvd_trunc_batch(
    vols: jnp.ndarray,
    variance_fraction: float = DEFAULT_VARIANCE_FRACTION_3D,
    use_kernel: bool = False,
    tune: KT.TuneConfig | None = None,
) -> jnp.ndarray:
    """``hosvd_trunc`` for a (k, d, m, n) stack of volumes (any rank >= 4):
    per-mode unfoldings computed as ONE batched Gram + batched ``eigvalsh``
    per mode, instead of the per-mode/per-volume Python loops.

    Each volume is mean-corrected by its own global mean (the same
    correction the scalar path applies), and a zero-variance mode (constant
    volume) yields fraction 1/p -- the ``jnp.where(total > 0, ..., 1.0)``
    guard ``svd_trunc_batch`` uses -- so the result stays in (0, 1].
    Returns a (k,) vector: the mean fraction across modes per volume.
    """
    if vols.ndim < 4:
        raise ValueError(
            f"hosvd_trunc_batch expects a (k, d, m, n) volume stack "
            f"(rank >= 4), got {vols.shape}; wrap one volume as x[None]")
    x = vols.astype(jnp.float32)
    x = x - jnp.mean(x, axis=tuple(range(1, x.ndim)), keepdims=True)
    fracs = []
    for mode in range(x.ndim - 1):
        u = _unfold_batch(x, mode)
        _, p, q = u.shape
        if use_kernel:
            from repro.kernels.gram import ops as gram_ops
            g = gram_ops.gram_batched(u, transpose=p >= q, tune=tune)
        else:
            g = (jnp.einsum("kai,kaj->kij", u, u, precision=_F32) if p >= q
                 else jnp.einsum("kia,kja->kij", u, u, precision=_F32))
        ev = jnp.maximum(jnp.linalg.eigvalsh(g), 0.0)[:, ::-1]   # descending
        total = jnp.sum(ev, axis=1, keepdims=True)
        cum = jnp.cumsum(ev, axis=1)
        frac = jnp.where(total > 0, cum / jnp.maximum(total, 1e-30), 1.0)
        needed = 1 + jnp.sum(frac < variance_fraction, axis=1)
        fracs.append(needed.astype(jnp.float32) / ev.shape[1])
    return jnp.mean(jnp.stack(fracs), axis=0)


def hosvd_trunc(
    x: jnp.ndarray,
    variance_fraction: float = DEFAULT_VARIANCE_FRACTION_3D,
    use_kernel: bool = False,
    tune: KT.TuneConfig | None = None,
) -> jnp.ndarray:
    """HOSVD-based truncation statistic for an N-D tensor (paper section 3.1.2).

    For each mode, unfold and compute the fraction of singular values whose
    squared mass reaches ``variance_fraction``; returns the mean fraction
    across modes (scalar in (0, 1] -- a constant tensor yields the mean of
    1/p over modes, not (1+p)/p).  The k=1 case of ``hosvd_trunc_batch``
    (single implementation, bit-exact with the batch path)."""
    if x.ndim < 3:
        raise ValueError(f"hosvd_trunc expects >=3-D tensor, got {x.shape}")
    return hosvd_trunc_batch(x[None], variance_fraction,
                             use_kernel=use_kernel, tune=tune)[0]


# ---------------------------------------------------------------------------
# Entropy / quantized entropy
# ---------------------------------------------------------------------------

def _entropy_from_counts(counts: jnp.ndarray) -> jnp.ndarray:
    n = jnp.maximum(jnp.sum(counts), 1)
    p = counts / n
    return -jnp.sum(jnp.where(p > 0, p * f32.log2(jnp.maximum(p, 1e-30)), 0.0))


def quantized_codes(x: jnp.ndarray, eps: float) -> jnp.ndarray:
    """Linear quantization codes ``floor(d/eps)`` as int32 (paper section 3.1.5).

    Raises ``ValueError`` for ``eps <= 0`` (concrete values), and clamps
    the float codes to the int32 range before the cast so extreme
    (value, eps) pairs saturate instead of silently wrapping.
    """
    _validate_eps_positive(eps)
    return quant.codes(x, eps)


def quantized_entropy(
    x: jnp.ndarray,
    eps: float,
    num_bins: int = 65536,
    use_kernel: bool = False,
    tune: KT.TuneConfig | None = None,
) -> jnp.ndarray:
    """Shannon entropy (bits/symbol) of the linearly quantized data.

    The code domain is data-dependent and unbounded, so for a jittable
    implementation we histogram the *shifted* codes into ``num_bins`` bins;
    codes beyond the range are hashed (mod) into the table.  For all datasets
    in the study the code range at the studied error bounds fits well within
    2^16 bins, making this exact (tests verify against a bincount oracle).
    """
    x = x.astype(jnp.float32).reshape(-1)
    codes = quantized_codes(x, eps)
    if use_kernel:
        from repro.kernels.qent import ops as qent_ops
        return qent_ops.quantized_entropy(x, eps, num_bins=num_bins, tune=tune)
    lo = jnp.min(codes)
    shifted = (codes - lo) % num_bins
    counts = jnp.zeros((num_bins,), jnp.int32).at[shifted].add(1)
    return _entropy_from_counts(counts)


def entropy(x: jnp.ndarray, num_bins: int = 65536) -> jnp.ndarray:
    """Entropy of raw float bit patterns, binned (lossless-style entropy)."""
    x = x.reshape(-1)
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    idx = (bits % jnp.uint32(num_bins)).astype(jnp.int32)
    counts = jnp.zeros((num_bins,), jnp.int32).at[idx].add(1)
    return _entropy_from_counts(counts)


# ---------------------------------------------------------------------------
# Feature bundle
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PredictorConfig:
    variance_fraction_2d: float = DEFAULT_VARIANCE_FRACTION_2D
    variance_fraction_3d: float = DEFAULT_VARIANCE_FRACTION_3D
    qent_bins: int = 65536
    use_kernels: bool = False  # route hot spots through Pallas kernels
    # kernel tile policy: defaults consult the backend's tuned table
    # (kernels/tuned/<backend>.json); frozen+hashable so it rides jit
    # static args and the serving layer's executable signatures
    tune: KT.TuneConfig = KT.TuneConfig()


def _qent_feature(qe):
    """log(q-ent), guarded: q-ent can be 0 (all values in one bin)."""
    return f32.log(jnp.maximum(qe, 1e-3))


def _ratio_feature(sv, sigma):
    """log(truncation level / sigma), guarded: sigma can be 0."""
    return f32.log(jnp.maximum(sv, 1e-6) / jnp.maximum(sigma, 1e-12))


def features_2d(x: jnp.ndarray, eps: float, cfg: PredictorConfig = PredictorConfig()) -> jnp.ndarray:
    """The paper's predictor vector for one 2-D slice at error bound ``eps``:
    ``[log(q_ent), log(svd_trunc / sigma)]`` (both standardized downstream).
    """
    sigma = jnp.std(x.astype(jnp.float32))
    sv = svd_trunc(x, cfg.variance_fraction_2d, use_kernel=cfg.use_kernels,
                   tune=cfg.tune)
    qe = quantized_entropy(x, eps, cfg.qent_bins, use_kernel=cfg.use_kernels,
                           tune=cfg.tune)
    log_qe = _qent_feature(qe)
    log_ratio = _ratio_feature(sv, sigma)
    return jnp.stack([log_qe, log_ratio])


def features_3d(x: jnp.ndarray, eps: float, cfg: PredictorConfig = PredictorConfig()) -> jnp.ndarray:
    sigma = jnp.std(x.astype(jnp.float32))
    sv = hosvd_trunc(x, cfg.variance_fraction_3d, use_kernel=cfg.use_kernels,
                     tune=cfg.tune)
    qe = quantized_entropy(x, eps, cfg.qent_bins, use_kernel=cfg.use_kernels,
                           tune=cfg.tune)
    log_qe = _qent_feature(qe)
    log_ratio = _ratio_feature(sv, sigma)
    return jnp.stack([log_qe, log_ratio])


def features_batch(slices: jnp.ndarray, eps: float, cfg: PredictorConfig = PredictorConfig()) -> jnp.ndarray:
    """vmapped featurizer over a stack of 2-D slices: (k, m, n) -> (k, 2)."""
    fn = functools.partial(features_2d, eps=eps, cfg=cfg)
    return jax.vmap(fn)(slices)


# ---------------------------------------------------------------------------
# Sweep-native batched featurization engine
#
# The production workload is a *sweep*: k slices x e error bounds (UC1
# probes a grid of ebs; UC2 shares features across compressors; training
# fits one model per grid eb).  The SVD predictor is eb-independent, so the
# engine computes it ONCE per slice via a single batched Gram + batched
# eigvalsh, and the q-ent predictor reads each slice once while quantizing
# at every error bound (fused multi-eps histogram) -- O(1) data reads per
# slice instead of the looped path's O(e).
# ---------------------------------------------------------------------------

def svd_trunc_batch(
    slices: jnp.ndarray,
    variance_fraction: float = DEFAULT_VARIANCE_FRACTION_2D,
    use_kernel: bool = False,
    tune: KT.TuneConfig | None = None,
) -> jnp.ndarray:
    """svd_trunc for a (k, m, n) stack in one batched Gram + eigvalsh."""
    if slices.ndim != 3:
        raise ValueError(f"svd_trunc_batch expects (k, m, n), got {slices.shape}")
    x = slices.astype(jnp.float32)
    x = x - jnp.mean(x, axis=1, keepdims=True)   # mean-corrected columns
    _, m, n = x.shape
    if use_kernel:
        from repro.kernels.gram import ops as gram_ops
        g = gram_ops.gram_batched(x, transpose=m >= n, tune=tune)
    else:
        g = (jnp.einsum("kai,kaj->kij", x, x, precision=_F32) if m >= n
             else jnp.einsum("kia,kja->kij", x, x, precision=_F32))
    ev = jnp.maximum(jnp.linalg.eigvalsh(g), 0.0)[:, ::-1]   # descending
    p = ev.shape[1]
    total = jnp.sum(ev, axis=1, keepdims=True)
    cum = jnp.cumsum(ev, axis=1)
    frac = jnp.where(total > 0, cum / jnp.maximum(total, 1e-30), 1.0)
    needed = 1 + jnp.sum(frac < variance_fraction, axis=1)
    return needed.astype(jnp.float32) / p


def _sort_f32_fast(x: jnp.ndarray) -> jnp.ndarray:
    """Ascending last-axis sort of f32 data via an order-preserving
    uint32 key (~4x faster than XLA's CPU float comparator sort).

    The key map is bijective -- negatives flip all bits, positives set
    the sign bit -- and inverted after the sort, so the output carries
    the EXACT input bit patterns and equals ``jnp.sort`` on non-NaN data
    (including -0.0 < +0.0; ties need no stability, there is no payload).
    """
    u = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    key = jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(0x80000000))
    sk = jax.lax.sort(key, dimension=-1, is_stable=False)
    v = jnp.where(sk >> 31 == 1, sk & jnp.uint32(0x7FFFFFFF), ~sk)
    return jax.lax.bitcast_convert_type(v, jnp.float32)


def quantized_entropy_sweep(
    slices: jnp.ndarray,
    epss: jnp.ndarray,
    num_bins: int = 65536,
    use_kernel: bool = False,
    tune: KT.TuneConfig | None = None,
) -> jnp.ndarray:
    """q-ent of a (k, ...) stack at an (e,) eb vector -> (k, e), reading
    the data once.

    Kernel route: the fused multi-eps Pallas histogram (``num_bins``
    hashed bins, one launch).  jnp route: sort each slice ONCE (shared by
    every error bound -- floor(x/eps) is monotone in x), then per-eps
    run-length counts from pure cumulative ops: no scatter, no histogram
    table.  The sort route is *exact*; it equals the hashed-histogram
    paths whenever the code range fits the bins (the study's validated
    regime, where those paths are exact too).
    """
    _validate_eps_positive(epss)
    k = slices.shape[0]
    flat = slices.astype(jnp.float32).reshape(k, -1)
    epss = jnp.asarray(epss, jnp.float32).reshape(-1)
    if use_kernel:
        from repro.kernels.qent import ops as qent_ops
        return qent_ops.quantized_entropy_sweep(flat, epss, num_bins=num_bins,
                                                tune=tune)
    n = flat.shape[1]
    xs = _sort_f32_fast(flat)                         # once, shared by all ebs
    iota = jnp.arange(n)
    ones = jnp.ones((k, 1), bool)

    def one_eps(eps):
        # lax.map over ebs keeps the peak working set at (k, n) -- the
        # same order as one step of the looped baseline -- instead of
        # materializing (k, e, n) temporaries for the whole sweep.
        codes = quant.codes(xs, eps)
        new = codes[:, 1:] != codes[:, :-1]
        start = jnp.concatenate([ones, new], axis=1)  # run starts, (k, n)
        end = jnp.concatenate([new, ones], axis=1)    # run ends
        run_start = jax.lax.cummax(jnp.where(start, iota, 0), axis=1)
        # H = log2(n) - (1/n) sum_runs L*log2(L).  A run's last element
        # has rank L inside the run, so one forward cummax (the rank)
        # replaces any backward pass or per-run reduction.
        j = (iota - run_start + 1).astype(jnp.float32)
        s = jnp.sum(jnp.where(end, j * f32.log2(j), 0.0), axis=1)
        return jnp.float32(math.log2(n)) - s / n

    return jax.lax.map(one_eps, epss).T               # (e, k) -> (k, e)


def variance_fraction_for(cfg: PredictorConfig, stack_ndim: int) -> float:
    """The truncation variance fraction a (k, ...) stack featurizes with:
    2-D slices (rank-3 stacks) use ``variance_fraction_2d``, volumes
    (rank >= 4) the HOSVD ``variance_fraction_3d``."""
    return (cfg.variance_fraction_2d if stack_ndim == 3
            else cfg.variance_fraction_3d)


# Trailing-axis width of the sweep tensor per mode: "features" emits the
# (log q-ent, log trunc-ratio) predictor pair, "quality" the (PSNR,
# NRMSE) pair of the quantization proxy, "both" their concatenation
# [log_qe, log_ratio, psnr, nrmse] from ONE read of the data.
SWEEP_MODE_WIDTHS = {"features": 2, "quality": 2, "both": 4}


def _features_sweep_impl(slices, epss, *, vf, bins, use_kernels, tune=None,
                         mode="features"):
    """Pure sweep body: (k, m, n) | (k, d, m, n) x (e,) -> (k, e, w).

    Rank-dispatching: rank-3 stacks run the batched 2-D SVD predictor,
    rank-4+ stacks the batched HOSVD predictor (``hosvd_trunc_batch``);
    the q-ent sweep flattens each element and is shared as-is.

    ``mode`` selects the trailing axis (``SWEEP_MODE_WIDTHS``):
    "features" is the paper's predictor pair, "quality" the fused
    PSNR/NRMSE pair (``kernels/quality``), "both" their concatenation --
    the one-pass ratio-quality frontier (a single tensor keeps the
    shard_map out_specs/masking width-agnostic).

    Kept jit-free so the distributed layer (``repro.dist.sweep``) can call
    it inside a ``shard_map`` body on each device's local slice shard.
    """
    if mode not in SWEEP_MODE_WIDTHS:
        raise ValueError(f"unknown sweep mode {mode!r}; expected one of "
                         f"{sorted(SWEEP_MODE_WIDTHS)}")
    x = slices.astype(jnp.float32)
    outs = []
    if mode in ("features", "both"):
        sigma = jnp.std(x, axis=tuple(range(1, x.ndim)))
        if x.ndim == 3:
            sv = svd_trunc_batch(x, vf, use_kernel=use_kernels, tune=tune)
        else:
            sv = hosvd_trunc_batch(x, vf, use_kernel=use_kernels, tune=tune)
        log_ratio = _ratio_feature(sv, sigma)
        qe = quantized_entropy_sweep(x, epss, bins, use_kernel=use_kernels,
                                     tune=tune)
        log_qe = _qent_feature(qe)                          # (k, e)
        outs.append(jnp.stack(
            [log_qe, jnp.broadcast_to(log_ratio[:, None], log_qe.shape)],
            axis=-1))
    if mode in ("quality", "both"):
        from repro.kernels.quality import ops as quality_ops
        outs.append(quality_ops.quality_sweep(
            x, epss, use_kernel=use_kernels))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=-1)


_features_sweep_traced = jax.jit(
    _features_sweep_impl,
    static_argnames=("vf", "bins", "use_kernels", "tune", "mode"))

# zero-copy variant for the serving hot path: the caller hands over the
# (padded) input stack and XLA may reuse its buffer for intermediates.
# Identical computation -- donation changes buffer lifetime, not math --
# so it shares _features_sweep_impl and tests assert bit-equality.
_features_sweep_donated = jax.jit(
    _features_sweep_impl,
    static_argnames=("vf", "bins", "use_kernels", "tune", "mode"),
    donate_argnums=(0,))


def features_sweep(
    slices: jnp.ndarray,
    epss,
    cfg: PredictorConfig = PredictorConfig(),
    *,
    sharded: bool | None = None,
    mesh=None,
    gather: bool = True,
    quality: bool = False,
) -> jnp.ndarray:
    """The full predictor tensor in one pass: (k, m, n) x (e,) -> (k, e, 2).

    Volumes are first-class: a rank-4 (k, d, m, n) stack routes the
    eb-independent column through the batched HOSVD predictor
    (``hosvd_trunc_batch``) instead of the 2-D SVD, same output shape.

    Column [..., 0] is log(q-ent) (eb-dependent, fused multi-eps
    histogram); column [..., 1] is log(svd_trunc / sigma) (for volumes
    log(hosvd_trunc / sigma); eb-independent, computed once and
    broadcast).  Matches looped ``features_2d`` / ``features_3d`` to f32
    tolerance (regression-tested).

    Distribution: with ``sharded=None`` (default) the sweep automatically
    runs as a ``shard_map`` over the slice axis whenever a mesh whose
    "slices"-mapped axis has extent > 1 is active (``dist.sharding.use_mesh``)
    or passed as ``mesh``; ``sharded=False`` forces the single-device path
    and ``sharded=True`` requires a mesh (raising if none is usable).
    ``gather=False`` returns the padded per-device result still sharded
    over the mesh (see ``repro.dist.sweep.features_sweep_sharded``).

    ``quality=True`` makes the same single pass also emit the fused
    PSNR/NRMSE tensor of the quantization proxy (``kernels/quality``)
    and returns the pair ``(features (k, e, 2), quality (k, e, 2))`` --
    both halves of the ratio-quality frontier from one read of the data.
    """
    out = _sweep_dispatch(slices, epss, cfg, sharded=sharded, mesh=mesh,
                          gather=gather,
                          mode="both" if quality else "features")
    if quality:
        return out[..., :2], out[..., 2:]
    return out


def quality_sweep(
    slices: jnp.ndarray,
    epss,
    cfg: PredictorConfig = PredictorConfig(),
    *,
    sharded: bool | None = None,
    mesh=None,
    gather: bool = True,
) -> jnp.ndarray:
    """The quality half of the frontier: (k, ...) x (e,) -> (k, e, 2).

    Column [..., 0] is the PSNR (dB) and [..., 1] the NRMSE of the
    quantization proxy (quantize-dequantize at each error bound, same
    saturating quantizer as the q-ent predictor), computed by the fused
    ``kernels/quality`` sweep in one read of the data.  Sharding routes
    exactly like :func:`features_sweep` (same auto-mesh rules), and the
    tensor is bitwise identical across the jnp reference, the Pallas
    kernel, sharded, streamed, and served paths.
    """
    return _sweep_dispatch(slices, epss, cfg, sharded=sharded, mesh=mesh,
                           gather=gather, mode="quality")


def _sweep_dispatch(slices, epss, cfg, *, sharded, mesh, gather, mode):
    """Shared routing for the mode-selected sweeps (validation, auto-
    sharding, single-device fallthrough)."""
    if slices.ndim not in (3, 4):
        raise ValueError(
            f"features_sweep expects a (k, m, n) slice stack or a "
            f"(k, d, m, n) volume stack, got {slices.shape}; wrap a single "
            f"slice/volume as x[None]")
    _validate_eps_positive(epss)
    epss = jnp.asarray(epss, jnp.float32).reshape(-1)
    # Auto-routing skips k=1: a single slice has no parallelism to split,
    # so sharding would only broadcast redundant copies of the same work
    # (UC1/UC2 featurize one query slice at a time under a serving mesh).
    if sharded or (sharded is None and slices.shape[0] > 1):
        from repro.dist import sweep as dsweep
        use_mesh = dsweep.active_sweep_mesh(mesh)
        if sharded and use_mesh is None:
            raise ValueError(
                "features_sweep(sharded=True) needs a mesh with a "
                "'slices'-mapped axis of extent > 1 (pass mesh= or "
                "activate one via dist.sharding.use_mesh)")
        if use_mesh is not None:
            return dsweep.features_sweep_sharded(
                slices, epss, cfg, mesh=use_mesh, gather=gather, mode=mode)
    return _features_sweep_traced(
        slices, epss, vf=variance_fraction_for(cfg, slices.ndim),
        bins=cfg.qent_bins, use_kernels=cfg.use_kernels, tune=cfg.tune,
        mode=mode)


@functools.partial(jax.jit, static_argnames=("bins", "use_kernels", "tune"))
def _qent_sweep_traced(x, epss, *, bins, use_kernels, tune=None):
    return quantized_entropy_sweep(x[None], epss, bins, use_kernel=use_kernels,
                                   tune=tune)[0]


@functools.partial(jax.jit, static_argnames=("vf", "use_kernels", "tune"))
def _svd_sigma_traced(x, *, vf, use_kernels, tune=None):
    if x.ndim == 2:
        sv = svd_trunc_batch(x[None], vf, use_kernel=use_kernels, tune=tune)[0]
    else:
        sv = hosvd_trunc_batch(x[None], vf, use_kernel=use_kernels,
                               tune=tune)[0]
    return sv, jnp.std(x.astype(jnp.float32))


class SliceCache:
    """Featurization cache for ONE slice or volume (UC1/UC2 cost
    structure): the eps-independent SVD-or-HOSVD/sigma part is computed at
    most once; q-ent is memoized per error bound; ``prefetch`` fills the
    memo for a whole eb grid with a single fused sweep (truncation
    predictor once + e histograms, one read)."""

    def __init__(self, x: jnp.ndarray, cfg: PredictorConfig):
        self._x = x
        self._cfg = cfg
        self._memo: dict = {}
        self._log_ratio = None

    @staticmethod
    def _key(eps) -> float:
        # features are computed in f32, so memoize at f32 resolution --
        # a float64 grid eb and its f32 round-trip must hit the same entry
        return float(jnp.float32(eps))

    def _ratio(self) -> jnp.ndarray:
        if self._log_ratio is None:
            sv, sigma = _svd_sigma_traced(
                self._x,
                vf=variance_fraction_for(self._cfg, self._x.ndim + 1),
                use_kernels=self._cfg.use_kernels, tune=self._cfg.tune)
            self._log_ratio = _ratio_feature(sv, sigma)
        return self._log_ratio

    def prefetch(self, epss) -> jnp.ndarray:
        """Featurize the whole eb grid in one sweep; returns (e, 2)."""
        feats = features_sweep(self._x[None], epss, self._cfg)[0]
        return self.seed(epss, feats)

    def seed(self, epss, feats) -> jnp.ndarray:
        """Preload externally computed features: ``feats[i]`` is the (2,)
        feature vector of this slice at ``epss[i]``.

        The hook the coalescing sweep service uses to hand a request rows
        from a shared batched launch (or the cross-request feature cache)
        instead of featurizing again; the seeded cache is bit-identical to
        one filled by :meth:`prefetch` because coalesced sweep rows are
        row-independent.
        """
        epss = jnp.asarray(epss).reshape(-1)
        if len(epss) != len(feats):
            raise ValueError(
                f"seed needs one feature row per eb: {len(epss)} ebs vs "
                f"{len(feats)} rows")
        for i, eps in enumerate(epss):
            self._memo[self._key(eps)] = feats[i]
        if len(feats):
            self._log_ratio = feats[0][1]
        return feats

    def __call__(self, eps) -> jnp.ndarray:
        _validate_eps_positive(eps)
        key = self._key(eps)
        if key not in self._memo:
            qe = _qent_sweep_traced(
                self._x, jnp.asarray([key], jnp.float32),
                bins=self._cfg.qent_bins,
                use_kernels=self._cfg.use_kernels, tune=self._cfg.tune)[0]
            self._memo[key] = jnp.stack(
                [_qent_feature(qe), self._ratio()])
        return self._memo[key]


class FeaturizationEngine:
    """Batched, sweep-native featurizer -- the single entry point the
    pipeline, use cases, and benchmarks route through.

    * ``sweep(slices, epss)``  -- (k, m, n) x (e,) -> (k, e, 2), one pass.
    * ``features(slices, eps)`` -- (k, 2): the e=1 column of the sweep.
    * ``cached(x)``            -- per-slice :class:`SliceCache`.

    Volumes are first-class: every entry point also accepts a
    (k, d, m, n) volume stack (``cached``: a single (d, m, n) volume) and
    routes the eb-independent column through ``hosvd_trunc_batch`` --
    per-mode unfoldings as batched Grams + batched ``eigvalsh`` -- with
    ``variance_fraction_3d``; shapes, sharding, and caching behave
    identically to the 2-D path.

    Distributed sweeps
    ------------------
    ``sweep``/``features`` shard the slice axis across every device of an
    active mesh (logical axis "slices" -> physical "data"; see
    ``repro.dist.sweep``).  Nothing changes at the call site beyond
    activating a mesh -- on a multi-device host (or a CPU dev box with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` exported before
    jax is imported)::

        from repro.dist import sharding as S
        from repro.launch import mesh as M
        engine = get_engine()
        with S.use_mesh(M.make_sweep_mesh()):
            feats = engine.sweep(slices, ebs)    # shard_map over slices

    Slice counts that don't divide the mesh are padded (and the pad
    dropped from the gathered result); ``gather=False`` keeps the padded
    result sharded for downstream stages that stay distributed.  The
    sharded sweep matches the single-device engine to f32 tolerance
    (asserted by tests/test_dist_sweep.py and bench_sweep_sharded).
    """

    def __init__(self, cfg: PredictorConfig = PredictorConfig()):
        self.cfg = cfg

    def sweep(self, slices: jnp.ndarray, epss, *, sharded: bool | None = None,
              mesh=None, gather: bool = True,
              quality: bool = False) -> jnp.ndarray:
        """One-pass predictor tensor; ``quality=True`` returns the
        ``(features, quality)`` pair from the same single read (the
        fused ratio-quality frontier, see :func:`features_sweep`)."""
        return features_sweep(slices, epss, self.cfg, sharded=sharded,
                              mesh=mesh, gather=gather, quality=quality)

    def quality(self, slices: jnp.ndarray, epss, *,
                sharded: bool | None = None, mesh=None,
                gather: bool = True) -> jnp.ndarray:
        """The (k, e, 2) PSNR/NRMSE tensor alone (:func:`quality_sweep`)."""
        return quality_sweep(slices, epss, self.cfg, sharded=sharded,
                             mesh=mesh, gather=gather)

    def features(self, slices: jnp.ndarray, eps: float, *,
                 sharded: bool | None = None, mesh=None) -> jnp.ndarray:
        return self.sweep(slices, [eps], sharded=sharded, mesh=mesh)[:, 0, :]

    def stream(self, source, name: str, epss, *, stream=None, mesh=None,
               digest=None, quality: bool = False):
        """Out-of-core sweep of one :class:`repro.data.source.
        DatasetSource` variable: chunked, double-buffered, bit-equal to
        ``sweep(source.read(name), epss)`` with at most one budgeted
        chunk resident (see ``repro.core.stream.stream_features``).
        ``quality=True`` returns the streamed ``(features, quality)``
        pair from the same chunk launches."""
        from repro.core import stream as ST
        return ST.stream_features(source, name, epss, self.cfg,
                                  stream=stream, mesh=mesh, digest=digest,
                                  quality=quality)

    def cached(self, x: jnp.ndarray, *, features=None, epss=None) -> SliceCache:
        """Per-slice cache; ``features``/``epss`` pre-seed it with
        externally supplied feature rows (see :meth:`SliceCache.seed`) so
        serving layers can reuse coalesced-launch / cross-request results."""
        c = SliceCache(x, self.cfg)
        if features is not None:
            c.seed(epss, features)
        return c


_DEFAULT_ENGINE = FeaturizationEngine()


def get_engine(cfg: PredictorConfig = None) -> FeaturizationEngine:
    """The shared default engine (or a fresh one for a custom config)."""
    if cfg is None or cfg == _DEFAULT_ENGINE.cfg:
        return _DEFAULT_ENGINE
    return FeaturizationEngine(cfg)


# ---------------------------------------------------------------------------
# eps-cached featurization (UC1: "the SVD is independent of the error bound,
# we execute this code only once; q-ent and inference run per error bound")
# ---------------------------------------------------------------------------

def features_2d_cached(x: jnp.ndarray) -> SliceCache:
    """Compat wrapper: per-slice cache from the default engine.  Returns a
    callable evaluating the full feature vector at any error bound, with
    the eps-independent parts computed once."""
    return _DEFAULT_ENGINE.cached(x)
