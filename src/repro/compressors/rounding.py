"""Rounding-based compressors: Bit Grooming and Digit Rounding.

Both operate on IEEE-754 mantissas and rely on a downstream lossless coder
(zstd here) -- they have no spatial decorrelation step, which is exactly why
the paper finds the *quantized entropy* dominates their CR prediction.

Absolute-error-bound operation follows the paper's OptZConfig mapping: the
number of mantissa bits kept is derived from the requested eps.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.compressors import base, lossless


def _bits(x: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)


def _floats(b: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.bitcast_convert_type(b.astype(jnp.uint32), jnp.float32)


@base.compiled("bitgrooming")
def _groom(data, eps):
    """Alternately shaved and set low mantissa bits, and the count of
    them, ``k``: masking k bits of a value with exponent e errs by
    < 2^(e-23+k), bounded by the field's largest exponent."""
    data = data.astype(jnp.float32)
    amax = jnp.max(jnp.abs(data))
    emax = jnp.floor(jnp.log2(jnp.maximum(amax, 1e-38)))
    k = jnp.clip(23 + jnp.floor(jnp.log2(eps)) - emax, 0, 23).astype(jnp.uint32)
    b = _bits(data)
    mask = (~jnp.uint32(0)) << k
    flat_idx = jnp.arange(data.size).reshape(data.shape)
    groomed = jnp.where(flat_idx % 2 == 0, b & mask, b | (~mask))
    # keep exact zeros exact (grooming convention)
    groomed = jnp.where(b == 0, b, groomed)
    return _floats(groomed), k


@base.compiled("digitrounding")
def _round_digits(data, eps):
    step = jnp.exp2(jnp.floor(jnp.log2(eps)))  # largest pow2 <= eps
    return (jnp.round(data.astype(jnp.float32) / step) * step).astype(jnp.float32)


class BitGrooming(base.Compressor):
    """Zender 2016: alternately shave (to 0) and set (to 1) insignificant
    mantissa bits; the number of kept bits is global, derived from eps and
    the field's max exponent (OptZConfig absolute-bound mapping).  The
    groomed values are their own reconstruction."""
    name = "bitgrooming"

    def encode(self, data, eps):
        groomed, k = _groom(data, eps)
        return groomed, {"shape": data.shape, "keepbits": k}

    def decode(self, codes, aux, eps):
        return codes

    def size_bytes(self, codes, aux, eps):
        return lossless.raw_zstd_size_bytes(np.asarray(codes))


class DigitRounding(base.Compressor):
    """Delaunay et al. 2018: round (not truncate) to the eps-determined
    binary digit -- equivalent to rounding onto a power-of-two grid."""
    name = "digitrounding"

    def encode(self, data, eps):
        return _round_digits(data, eps), {"shape": data.shape}

    def decode(self, codes, aux, eps):
        return codes

    def size_bytes(self, codes, aux, eps):
        return lossless.raw_zstd_size_bytes(np.asarray(codes))


base.register(BitGrooming())
base.register(DigitRounding())
