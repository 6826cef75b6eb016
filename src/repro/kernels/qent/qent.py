"""Fused quantize+histogram Pallas kernel for the quantized entropy.

The paper's q-ent predictor needs the histogram of ``floor(d / eps)``.
GPUs use atomics/hash maps; TPUs have no scatter in VMEM, so the codes
are hashed into ``B`` bins (``code mod B``, B a power of two) and
counted on the MXU.  Hash collisions only *lower* the measured entropy;
with B = 4096 and the paper's error bounds the code ranges fit in one
window so the hash is injective (tests assert exactness in that regime).

Counting scheme: a bin index splits as ``hi * L + lo`` with ``L =
min(B, 128)`` lanes and ``H = B / L`` rows.  For one (1, c) row of
codes, the one-hots ``A = (iota_H == hi)`` of shape (H, c) and ``O =
(iota_L == lo)`` of shape (L, c) keep the data on lanes (a sublane
broadcast, never a lane->sublane move), and ``A @ O^T`` is the (H, L)
histogram of that row.  0/1 operands are exact in bf16 and the f32
accumulator is exact below 2**24 counts per tile, so the int32
histogram is exact.  Peak live VMEM is O((H + L) * c), not O(B * c).

``qent_histogram_sweep`` is the sweep engine: a (k, n) stack of slices
x an (e,) vector of error bounds in ONE launch.  Each input tile is read
from HBM once and quantized at every error bound while resident in VMEM,
turning e full passes over the data into one.  The single-(slice, eps)
histogram is its (k=1, e=1) case (see ops.py).

Grid: (slices, tiles of the flattened input); histograms accumulate in
the output ref across grid steps (sequential TPU grid).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import backend
from repro import quant

DEFAULT_TILE = 2048
DEFAULT_BINS = 4096
LANES = 128

# The (e, B) int32 histogram block stays resident in VMEM for a whole
# slice and is double-buffered; ops.py splits wider eb vectors into
# launches whose block stays under this many bytes (4 MiB: 16 ebs at
# 65536 bins), well inside the compiler's default scoped VMEM.
MAX_HIST_BLOCK_BYTES = 4 * 2**20


def split_bins(bins: int) -> tuple[int, int]:
    """``bins`` (a power of two) -> (H rows, L lanes) of the histogram."""
    if bins < 1 or bins & (bins - 1):
        raise ValueError(f"qent kernel bins must be a power of two, got {bins}")
    lanes = min(bins, LANES)
    return bins // lanes, lanes


def max_eps_per_launch(bins: int) -> int:
    """Error bounds one launch may hold histograms for (see ops.py)."""
    return max(1, MAX_HIST_BLOCK_BYTES // (4 * bins))


def _hash_codes(x, eps, bins: int):
    """floor(x/eps) (int32-saturated) hashed into [0, bins): for a power
    of two ``bins`` the two's-complement mask IS the floored modulus.

    The clamp saturates instead of wrapping -- a wrapped code would
    scatter into an arbitrary histogram bin."""
    return quant.codes(x, eps) & (bins - 1)


def _tile_histogram(idx, rows: int, lanes: int):
    """(8, c) bin indices -> (rows, lanes) f32 counts (exact integers)."""
    c = idx.shape[1]
    shift = lanes.bit_length() - 1
    hi, lo = idx >> shift, idx & (lanes - 1)
    hi_iota = jax.lax.broadcasted_iota(jnp.int32, (rows, c), 0)
    lo_iota = jax.lax.broadcasted_iota(jnp.int32, (lanes, c), 0)
    acc = jnp.zeros((rows, lanes), jnp.float32)
    for s in range(idx.shape[0]):
        a = (hi_iota == hi[s:s + 1, :]).astype(jnp.bfloat16)    # (H, c)
        o = (lo_iota == lo[s:s + 1, :]).astype(jnp.bfloat16)    # (L, c)
        acc += jax.lax.dot_general(a, o, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)
    return acc


def _qent_sweep_kernel(eps_ref, x_ref, hist_ref, *, rows: int, lanes: int,
                       n_eps: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    x = x_ref[0]                                     # (8, tile/8): ONE read
    for ei in range(n_eps):                          # e histograms, 0 rereads
        idx = _hash_codes(x, eps_ref[ei], rows * lanes)
        hist_ref[0, ei] += _tile_histogram(idx, rows, lanes).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("tile", "bins"))
def qent_histogram_sweep(
    x: jnp.ndarray,
    epss: jnp.ndarray,
    tile: int = DEFAULT_TILE,
    bins: int = DEFAULT_BINS,
) -> jnp.ndarray:
    """(k, n) slice stack x (e,) error bounds -> (k, e, bins) histograms.

    One launch; grid = (k slices, n/tile tiles).  Each tile is quantized
    at all e error bounds while resident in VMEM.  Lowered for the TPU,
    the tile must hold whole 128-lane rows (``tile % 1024 == 0``).
    """
    k, n = x.shape
    (n_eps,) = epss.shape
    if n % tile or tile % 8:
        raise ValueError(f"qent tile {tile} must divide n={n} in 8 rows")
    interpret = backend.interpret()
    if not interpret and (tile // 8) % LANES:
        raise ValueError(
            f"qent tile {tile} is not lane-aligned on the TPU: tile/8 "
            f"must be a multiple of {LANES}")
    rows, lanes = split_bins(bins)
    xb = jnp.swapaxes(x.reshape(k, n // 8, 8), 1, 2)  # (k, 8, n/8)
    kernel = functools.partial(_qent_sweep_kernel, rows=rows, lanes=lanes,
                               n_eps=n_eps)
    hist = pl.pallas_call(
        kernel,
        grid=(k, n // tile),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 8, tile // 8), lambda s, t: (s, 0, t)),
        ],
        out_specs=pl.BlockSpec((1, n_eps, rows, lanes),
                               lambda s, t: (s, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((k, n_eps, rows, lanes), jnp.int32),
        interpret=interpret,
        name="qent_histogram",
    )(jnp.asarray(epss, jnp.float32), xb)
    return hist.reshape(k, n_eps, bins)
