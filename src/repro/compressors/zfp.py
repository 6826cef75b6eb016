"""ZFP-like transform compressor (fixed 4^n blocks, near-orthogonal lifting).

Pipeline per 4x4(x4) block (Lindstrom 2014):
  1. block-floating-point: align all values to the block's max exponent,
  2. integer forward lifting transform along each dimension,
  3. embedded bit-plane coding down to an eps-determined cutoff plane.

The integer lifting pair below is the exact fwd/inv lift from the zfp
codebase (arithmetic shifts on int32).  Size is computed from the bit-plane
cutoff analytically -- zfp's output is already entropy-packed, so no zstd
stage.  The forward transform has a Pallas kernel in
``repro.kernels.zfp_block``.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.compressors import base, lossless

INTPREC = 26          # fixed-point precision for fp32 inputs


def _guard_bits(ndim: int) -> int:
    """Transform-gain guard: the inverse lifting amplifies per-coefficient
    truncation error by < 2^(1+ndim) in the worst case (measured + margin)."""
    return 1 + ndim


# ---------------------------------------------------------------------------
# Exact zfp integer lifting (4-vectors)
# ---------------------------------------------------------------------------

def fwd_lift4(v: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Forward lift along ``axis`` (length 4), int32 arithmetic shifts."""
    x, y, z, w = jnp.moveaxis(v, axis, 0)
    x = x + w; x = x >> 1; w = w - x
    z = z + y; z = z >> 1; y = y - z
    x = x + z; x = x >> 1; z = z - x
    w = w + y; w = w >> 1; y = y - w
    w = w + (y >> 1); y = y - (w >> 1)
    return jnp.moveaxis(jnp.stack([x, y, z, w]), 0, axis)


def inv_lift4(v: jnp.ndarray, axis: int) -> jnp.ndarray:
    x, y, z, w = jnp.moveaxis(v, axis, 0)
    y = y + (w >> 1); w = w - (y >> 1)
    y = y + w; w = w << 1; w = w - y
    z = z + x; x = x << 1; x = x - z
    y = y + z; z = z << 1; z = z - y
    w = w + x; x = x << 1; x = x - w
    return jnp.moveaxis(jnp.stack([x, y, z, w]), 0, axis)


# ---------------------------------------------------------------------------
# Blocking
# ---------------------------------------------------------------------------

def _pad4(data: jnp.ndarray):
    pads = [(0, (-s) % 4) for s in data.shape]
    return jnp.pad(data, pads, mode="edge"), data.shape


def _to_blocks4(x: jnp.ndarray) -> jnp.ndarray:
    if x.ndim == 2:
        m, n = x.shape
        return x.reshape(m // 4, 4, n // 4, 4).transpose(0, 2, 1, 3).reshape(-1, 4, 4)
    m, n, k = x.shape
    return (x.reshape(m // 4, 4, n // 4, 4, k // 4, 4)
             .transpose(0, 2, 4, 1, 3, 5).reshape(-1, 4, 4, 4))


def _from_blocks4(blocks: jnp.ndarray, padded_shape) -> jnp.ndarray:
    if len(padded_shape) == 2:
        m, n = padded_shape
        return (blocks.reshape(m // 4, n // 4, 4, 4)
                .transpose(0, 2, 1, 3).reshape(m, n))
    m, n, k = padded_shape
    return (blocks.reshape(m // 4, n // 4, k // 4, 4, 4, 4)
            .transpose(0, 3, 1, 4, 2, 5).reshape(m, n, k))


# ---------------------------------------------------------------------------
# Encode / decode
# ---------------------------------------------------------------------------

def zfp_transform(data: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray, Tuple]:
    """Blocked block-floating-point + forward lifting.

    Returns (coeff int32 blocks, per-block exponent, padded_shape).
    """
    padded, shape = _pad4(data)
    blocks = _to_blocks4(padded.astype(jnp.float32))
    ndim = blocks.ndim - 1
    axes = tuple(range(1, ndim + 1))
    amax = jnp.max(jnp.abs(blocks), axis=axes)
    # block exponent e: 2^e >= amax  (frexp-style)
    e = jnp.ceil(jnp.log2(jnp.maximum(amax, 1e-38))).astype(jnp.int32)
    e = jnp.where(amax > 0, e, 0)
    scale = jnp.exp2((INTPREC - 2 - e).astype(jnp.float32))
    q = jnp.round(blocks * scale[(...,) + (None,) * ndim]).astype(jnp.int32)
    for axis in range(1, ndim + 1):
        q = fwd_lift4(q, axis)
    return q, e, padded.shape


def zfp_untransform(q: jnp.ndarray, e: jnp.ndarray, padded_shape, shape) -> jnp.ndarray:
    ndim = q.ndim - 1
    for axis in range(ndim, 0, -1):
        q = inv_lift4(q, axis)
    scale = jnp.exp2((e - (INTPREC - 2)).astype(jnp.float32))
    blocks = q.astype(jnp.float32) * scale[(...,) + (None,) * ndim]
    full = _from_blocks4(blocks, padded_shape)
    return full[tuple(slice(0, s) for s in shape)]


def _cutoff_plane(e: jnp.ndarray, eps: float, ndim: int) -> jnp.ndarray:
    """Integer bit-plane below which coefficients are dropped.

    LSB of the fixed-point representation is worth 2^(e - (INTPREC-2));
    dropping planes < k introduces error <= 2^k * lsb * transform gain.
    """
    lsb_log2 = e - (INTPREC - 2)
    k = jnp.floor(jnp.log2(eps)).astype(jnp.int32) - lsb_log2 - _guard_bits(ndim)
    return k  # may be negative -> keep everything


def zfp_truncate(q: jnp.ndarray, e: jnp.ndarray, eps: float) -> jnp.ndarray:
    ndim = q.ndim - 1
    k = jnp.maximum(_cutoff_plane(e, eps, ndim), 0)[(...,) + (None,) * ndim]
    step = (jnp.int32(1) << k)
    # round-to-nearest at plane k keeps the bound tight
    half = step >> 1
    return jnp.where(
        step > 1,
        jnp.sign(q) * (((jnp.abs(q) + half) >> k) << k),
        q,
    )


@base.compiled("zfp")
def zfp_size_bits(q: jnp.ndarray, e: jnp.ndarray, eps: float) -> jnp.ndarray:
    """Embedded-coding size model: per coefficient, bits above the cutoff
    plane + sign, plus per-block header (exponent + group tests)."""
    ndim = q.ndim - 1
    k = jnp.maximum(_cutoff_plane(e, eps, ndim), 0)[(...,) + (None,) * ndim]
    mag = jnp.abs(q)
    bitlen = jnp.where(mag > 0, jnp.ceil(jnp.log2(mag.astype(jnp.float32) + 1.0)), 0.0)
    kept = jnp.maximum(bitlen - k.astype(jnp.float32), 0.0)
    signs = (kept > 0).astype(jnp.float32)
    per_block = jnp.sum(kept + signs, axis=tuple(range(1, ndim + 1)))
    header = 8.0 + 2.0 * (4 ** ndim) / 4.0  # exponent + group-test bits
    return jnp.sum(per_block + header)


@base.compiled("zfp")
def _zfp_encode(data, eps):
    q, e, _ = zfp_transform(data)
    return zfp_truncate(q, e, eps), e


def _padded4(shape) -> Tuple[int, ...]:
    return tuple(s + (-s) % 4 for s in shape)


@base.compiled("zfp", static=("shape",))
def _zfp_decode(codes, e, *, shape):
    return zfp_untransform(codes, e, _padded4(shape), shape)


class ZFP(base.Compressor):
    name = "zfp"

    def encode(self, data, eps):
        qt, e = _zfp_encode(data, eps)
        return qt, {"e": e, "padded": _padded4(data.shape), "shape": data.shape}

    def decode(self, codes, aux, eps):
        return _zfp_decode(codes, aux["e"], shape=tuple(aux["shape"]))

    def size_bytes(self, codes, aux, eps):
        bits = float(zfp_size_bits(codes, aux["e"], eps))
        return int(np.ceil(bits / 8.0))


base.register(ZFP())
