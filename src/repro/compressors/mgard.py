"""MGARD-like multilevel (multigrid) compressor.

Hierarchical decomposition (Ainsworth et al.): the data is recursively
restricted to a coarse grid; fine-grid points are predicted by multilinear
interpolation of the *reconstructed* coarse grid and the multilevel
coefficients (prediction residuals) are uniformly quantized and entropy
coded (zstd).  Predicting from reconstructed values keeps the absolute
error bound exact at every point, mirroring MGARD's s=0 uniform-quantizer
mode.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.compressors import base, lossless
from repro.compressors.sz import interleave, dequantize, quantize_bounded


def _interp_even_to_full(coarse: jnp.ndarray, full_shape, axis: int) -> jnp.ndarray:
    """Linear interpolation from even-index samples to the full grid along
    ``axis`` (odd points = average of neighbours, edge clamped)."""
    c = jnp.moveaxis(coarse, axis, 0)
    nxt = jnp.concatenate([c[1:], c[-1:]], axis=0)
    odd = 0.5 * (c + nxt)
    out = interleave(c, odd[: full_shape[axis] // 2], 0)
    return jnp.moveaxis(out, 0, axis)


def _predict_fine(coarse: jnp.ndarray, fine_shape) -> jnp.ndarray:
    """Multilinear prolongation from the [::2,::2(,::2)] grid to fine_shape."""
    cur = coarse
    for axis in range(len(fine_shape)):
        cur = _interp_even_to_full(cur, fine_shape, axis)
    return cur


def _restrict(data: jnp.ndarray) -> jnp.ndarray:
    sl = tuple(slice(None, None, 2) for _ in data.shape)
    return data[sl]


@base.compiled("mgard", static=("levels",))
def _mgard_encode(data, eps, *, levels):
    """Root codes and the level codes, coarsest level first."""
    fines = [data.astype(jnp.float32)]
    for _ in range(levels):
        if min(fines[-1].shape) < 4:
            break
        fines.append(_restrict(fines[-1]))
    # Quantize from the coarsest level outward so predictions use
    # reconstructed values (exact error-bound preservation).
    root = quantize_bounded(fines.pop(), eps)
    recon = dequantize(root, eps)
    level_codes = []
    for fine in reversed(fines):
        pred = _predict_fine(recon, fine.shape)
        c = quantize_bounded(fine - pred, eps)
        level_codes.append(c)
        recon = pred + dequantize(c, eps)
    return root, level_codes


@base.compiled("mgard")
def _mgard_decode(root, level_codes, eps):
    recon = dequantize(root, eps)
    for c in level_codes:
        recon = _predict_fine(recon, c.shape) + dequantize(c, eps)
    return recon


class MGARD(base.Compressor):
    name = "mgard"
    levels = 4

    def encode(self, data, eps):
        root, level_codes = _mgard_encode(data, eps, levels=self.levels)
        shapes = [tuple(c.shape) for c in reversed(level_codes)]
        return (root, level_codes), {"shape": data.shape, "shapes": shapes}

    def decode(self, codes, aux, eps):
        root, level_codes = codes
        return _mgard_decode(root, level_codes, eps)

    def size_bytes(self, codes, aux, eps):
        root_codes, level_codes = codes
        total = lossless.coded_size_bytes(np.asarray(root_codes))
        for c in level_codes:
            total += lossless.coded_size_bytes(np.asarray(c))
        return total


base.register(MGARD())
