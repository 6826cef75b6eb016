"""SZ-family error-bounded lossy compressors (prediction-based decorrelation).

Three compressor-prediction schemes (paper section 4.2):
  * Lorenzo (SZ1/SZ3-lorenzo)      -- immediate-neighbour stencil predictor
  * Regression (SZ2/SZ3-regression)-- per 6x6(x6) block hyperplane fit
  * Interpolation (SZ3-interp)     -- multilevel cubic interpolation
plus SZ2's *dynamic* per-block selection between Lorenzo and regression.

TPU adaptation: classic SZ predicts from *reconstructed* neighbours, a
sequential data dependence.  We use the cuSZ dual-quantization formulation
for Lorenzo -- pre-quantize every value, then difference the integer codes --
which preserves the absolute error bound exactly and is fully parallel
(maps to the Pallas stencil kernel in ``repro.kernels.lorenzo``).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import numpy as np
import jax.numpy as jnp

from repro.compressors import base, lossless

BLOCK = 6  # SZ2 block size


# ---------------------------------------------------------------------------
# Dual-quantization Lorenzo (N-D)
# ---------------------------------------------------------------------------

def quantize_bounded(vals: jnp.ndarray, eps: float | jnp.ndarray) -> jnp.ndarray:
    """Integer codes q with |vals - 2*eps*q| <= eps *exactly*.

    ``round(vals / (2 eps))`` alone can flip a boundary by one ulp of the
    scaled value; real SZ handles this with an unpredictable-value check.
    We instead nudge the code by +-1 where the bound is violated -- exact,
    branch-free and parallel (same trick the Pallas kernel uses).
    """
    q = jnp.round(vals / (2.0 * eps)).astype(jnp.int32)
    for _ in range(2):  # two rounds: the nudge itself re-rounds the product
        err = vals - dequantize(q, eps)
        q = q + (err > eps).astype(jnp.int32) - (err < -eps).astype(jnp.int32)
    return q


def pinned(x: jnp.ndarray) -> jnp.ndarray:
    """``x`` unchanged, as an operand that no compiler fuses into a
    multiply-add with the add or subtract reading it: an FMA rounds once
    where op-by-op code rounds twice, so a product is pinned wherever the
    encoder's bound check, its reconstruction and the decoder must agree
    to the bit.  (A select on ``x == x``: XLA:CPU drops an
    ``optimization_barrier`` before it fuses.)"""
    return jnp.where(x == x, x, jnp.nan)


def dequantize(q: jnp.ndarray, eps: float | jnp.ndarray) -> jnp.ndarray:
    """The reconstruction ``2*eps*q``: the exact f32 product, pinned."""
    return pinned(q.astype(jnp.float32) * (2.0 * eps))


@base.compiled("sz3-lorenzo")
def lorenzo_encode(data: jnp.ndarray, eps: float) -> jnp.ndarray:
    """codes = prod_axis (1 - S_axis) q  (N-D integer Lorenzo difference)."""
    q = quantize_bounded(data, eps)
    for axis in range(data.ndim):
        shifted = jnp.roll(q, 1, axis=axis)
        # zero out the wrapped-around first slice
        idx = [slice(None)] * data.ndim
        idx[axis] = slice(0, 1)
        shifted = shifted.at[tuple(idx)].set(0)
        q = q - shifted
    return q


@base.compiled("sz3-lorenzo")
def lorenzo_decode(codes: jnp.ndarray, eps: float) -> jnp.ndarray:
    q = codes
    for axis in range(codes.ndim):
        q = jnp.cumsum(q, axis=axis)
    return dequantize(q, eps)


# ---------------------------------------------------------------------------
# Blockwise helpers
# ---------------------------------------------------------------------------

def _pad_to_multiple(data: jnp.ndarray, b: int) -> jnp.ndarray:
    return jnp.pad(data, [(0, (-s) % b) for s in data.shape], mode="edge")


def _to_blocks(x: jnp.ndarray, b: int) -> jnp.ndarray:
    """2-D (M,N) -> (nb, b, b); 3-D (M,N,K) -> (nb, b, b, b)."""
    if x.ndim == 2:
        m, n = x.shape
        x = x.reshape(m // b, b, n // b, b).transpose(0, 2, 1, 3)
        return x.reshape(-1, b, b)
    m, n, k = x.shape
    x = x.reshape(m // b, b, n // b, b, k // b, b).transpose(0, 2, 4, 1, 3, 5)
    return x.reshape(-1, b, b, b)


def _from_blocks(blocks: jnp.ndarray, padded_shape: Tuple[int, ...], b: int) -> jnp.ndarray:
    if len(padded_shape) == 2:
        m, n = padded_shape
        x = blocks.reshape(m // b, n // b, b, b).transpose(0, 2, 1, 3)
        return x.reshape(m, n)
    m, n, k = padded_shape
    x = blocks.reshape(m // b, n // b, k // b, b, b, b).transpose(0, 3, 1, 4, 2, 5)
    return x.reshape(m, n, k)


@functools.lru_cache(maxsize=None)
def _block_coords(b: int, ndim: int) -> np.ndarray:
    """Design matrix [1, i, j(, k)] for hyperplane regression: (b^ndim, ndim+1)."""
    grids = np.meshgrid(*[np.arange(b, dtype=np.float32)] * ndim, indexing="ij")
    cols = [np.ones((b,) * ndim, np.float32), *grids]
    return np.stack([c.reshape(-1) for c in cols], axis=1)


@functools.lru_cache(maxsize=None)
def _block_pinv(b: int, ndim: int) -> np.ndarray:
    """Pseudo-inverse of the design matrix, (ndim+1, b^ndim), made once
    on the host in float64."""
    return np.linalg.pinv(_block_coords(b, ndim).astype(np.float64)).astype(np.float32)


def _fit_planes(blocks: jnp.ndarray) -> jnp.ndarray:
    """Least-squares hyperplane per block: (nb, b..b) -> (nb, ndim+1)."""
    ndim = blocks.ndim - 1
    y = blocks.reshape(blocks.shape[0], -1)          # (nb, p)
    return y @ _block_pinv(blocks.shape[1], ndim).T  # (nb, ndim+1)


def _coef_step(eps):
    """The grid of the regression coefficients: SZ2 quantizes them too
    (eps/BLOCK keeps plane-eval error within eps/2).  A product, not a
    quotient: compiled XLA turns ``eps / BLOCK`` into ``eps * (1/BLOCK)``,
    op-by-op code would not."""
    return eps * np.float32(1.0 / BLOCK)


def _quantize_coefs(coefs: jnp.ndarray, eps) -> jnp.ndarray:
    return jnp.round(coefs / _coef_step(eps)).astype(jnp.int32)


def _plane_values(cq: jnp.ndarray, eps, ndim: int) -> jnp.ndarray:
    """The planes of quantized coefficients, (nb, b..b): c0 + c1*i +
    c2*j (+ c3*k), summed in that order with every product pinned, so
    that encoder and decoder get the same bits on every backend."""
    x = _block_coords(BLOCK, ndim)                   # (p, ndim+1)
    coefs = pinned(cq.astype(jnp.float32) * _coef_step(eps))
    planes = coefs[:, :1]
    for k in range(1, ndim + 1):
        planes = planes + pinned(coefs[:, k:k + 1] * x[:, k])
    return planes.reshape(cq.shape[0], *([BLOCK] * ndim))


def _padded_shape(shape: Tuple[int, ...], b: int) -> Tuple[int, ...]:
    return tuple(s + (-s) % b for s in shape)


# ---------------------------------------------------------------------------
# Per-block Lorenzo (parallel across blocks; used by SZ2's dynamic mode)
# ---------------------------------------------------------------------------

def _block_lorenzo_codes(qblocks: jnp.ndarray) -> jnp.ndarray:
    """Integer Lorenzo difference within each block (halo-free blocks)."""
    q = qblocks
    ndim = q.ndim - 1
    for axis in range(1, ndim + 1):
        shifted = jnp.roll(q, 1, axis=axis)
        idx = [slice(None)] * q.ndim
        idx[axis] = slice(0, 1)
        shifted = shifted.at[tuple(idx)].set(0)
        q = q - shifted
    return q


def _block_lorenzo_decode(codes: jnp.ndarray) -> jnp.ndarray:
    q = codes
    ndim = q.ndim - 1
    for axis in range(1, ndim + 1):
        q = jnp.cumsum(q, axis=axis)
    return q


# ---------------------------------------------------------------------------
# Compressors
# ---------------------------------------------------------------------------

class SZLorenzo(base.Compressor):
    """SZ3 with the exclusive Lorenzo scheme (dual-quantization form)."""
    name = "sz3-lorenzo"

    def encode(self, data, eps):
        return lorenzo_encode(data, eps), {"shape": data.shape}

    def decode(self, codes, aux, eps):
        return lorenzo_decode(codes, eps)

    def size_bytes(self, codes, aux, eps):
        return lossless.coded_size_bytes(np.asarray(codes))


@base.compiled("sz3-regression")
def _regression_encode(data, eps):
    blocks = _to_blocks(_pad_to_multiple(data, BLOCK), BLOCK)
    cq = _quantize_coefs(_fit_planes(blocks), eps)
    codes = quantize_bounded(blocks - _plane_values(cq, eps, data.ndim), eps)
    return codes, cq


@base.compiled("sz3-regression", static=("shape",))
def _regression_decode(codes, cq, eps, *, shape):
    blocks = _plane_values(cq, eps, len(shape)) + dequantize(codes, eps)
    full = _from_blocks(blocks, _padded_shape(shape, BLOCK), BLOCK)
    return full[tuple(slice(0, s) for s in shape)]


class SZRegression(base.Compressor):
    """SZ3 with the exclusive regression scheme (per-block hyperplane)."""
    name = "sz3-regression"

    def encode(self, data, eps):
        codes, cq = _regression_encode(data, eps)
        return codes, {"shape": data.shape,
                       "padded": _padded_shape(data.shape, BLOCK),
                       "coef_codes": cq}

    def decode(self, codes, aux, eps):
        return _regression_decode(codes, aux["coef_codes"], eps,
                                  shape=tuple(aux["shape"]))

    def size_bytes(self, codes, aux, eps):
        resid = lossless.coded_size_bytes(np.asarray(codes))
        coefb = lossless.coded_size_bytes(np.asarray(aux["coef_codes"]))
        return resid + coefb


# -- sz3-interp: multilevel cubic interpolation (2-D) -------------------------

def _interp_odd(even: jnp.ndarray, n_odd: int, axis: int) -> jnp.ndarray:
    """Predict values at odd indices from the even-index samples along
    ``axis`` with a 4-point cubic (falls back to linear at the edges)."""
    e = jnp.moveaxis(even, axis, 0)
    # neighbours e[i], e[i+1] surround odd point i; cubic uses i-1..i+2
    em1 = jnp.concatenate([e[:1], e[:-1]], axis=0)
    ep1 = jnp.concatenate([e[1:], e[-1:]], axis=0)
    ep2 = jnp.concatenate([e[2:], e[-1:], e[-1:]], axis=0)
    cubic = (-em1 + pinned(9.0 * e) + pinned(9.0 * ep1) - ep2) / 16.0
    return jnp.moveaxis(cubic[:n_odd], 0, axis)


def interleave(even: jnp.ndarray, odd: jnp.ndarray, axis: int) -> jnp.ndarray:
    """``even`` at the even indices along ``axis``, ``odd`` at the odd
    ones; ``even`` is as long as ``odd`` or one longer."""
    e, o = jnp.moveaxis(even, axis, 0), jnp.moveaxis(odd, axis, 0)
    n = e.shape[0] + o.shape[0]
    o = jnp.pad(o, [(0, e.shape[0] - o.shape[0])] + [(0, 0)] * (o.ndim - 1))
    out = jnp.stack([e, o], axis=1).reshape((2 * e.shape[0],) + e.shape[1:])
    return jnp.moveaxis(out[:n], 0, axis)


def _interp_fill(coarse, codes, eps, axis):
    """One half-step of the reconstruction: ``coarse`` at the even
    indices along ``axis``, the cubic prediction plus the dequantized
    ``codes`` at the odd ones.  Encoder and decoder both rebuild with it."""
    pred = _interp_odd(coarse, codes.shape[axis], axis)
    return interleave(coarse, pred + dequantize(codes, eps), axis)


def _interp_rec(data, eps, levels_left: int):
    """Multilevel encode; predictions are made from *reconstructed*
    values so the bound holds exactly at every level.

    Returns (root codes, [(codes_c, codes_r)] outermost level first, recon).
    """
    m, n = data.shape
    if levels_left == 0 or min(m, n) < 8:
        root = quantize_bounded(data, eps)
        return root, [], dequantize(root, eps)
    half = data[:, 0::2]                 # even columns (original)
    root, pairs, recon_coarse = _interp_rec(half[0::2, :], eps, levels_left - 1)
    # rows: predict odd rows of `half` from reconstructed coarse
    pred_r = _interp_odd(recon_coarse, m // 2, axis=0)
    codes_r = quantize_bounded(half[1::2, :] - pred_r, eps)
    recon_half = _interp_fill(recon_coarse, codes_r, eps, axis=0)
    # cols: predict odd columns of `data` from reconstructed half
    pred_c = _interp_odd(recon_half, n // 2, axis=1)
    codes_c = quantize_bounded(data[:, 1::2] - pred_c, eps)
    recon = _interp_fill(recon_half, codes_c, eps, axis=1)
    return root, [(codes_c, codes_r)] + pairs, recon


@base.compiled("sz3-interp", static=("levels",))
def _interp_encode(data, eps, *, levels):
    root, pairs, _ = _interp_rec(data.astype(jnp.float32), eps, levels)
    return root, pairs


@base.compiled("sz3-interp")
def _interp_decode(root, pairs, eps):
    recon = dequantize(root, eps)
    for codes_c, codes_r in reversed(pairs):
        recon = _interp_fill(_interp_fill(recon, codes_r, eps, axis=0),
                             codes_c, eps, axis=1)
    return recon


class SZInterp(base.Compressor):
    """SZ3 with the multilevel cubic-interpolation scheme (2-D).

    Codes are the tree ``("root", codes)`` or ``("level", sub, codes_c,
    codes_r, (m, n))``: the odd columns and the odd rows of the even
    columns of an (m, n) level, predicted from ``sub``."""
    name = "sz3-interp"
    supports_3d = False
    levels = 3

    def encode(self, data, eps):
        root, pairs = _interp_encode(data, eps, levels=self.levels)
        codes = ("root", root)
        for codes_c, codes_r in reversed(pairs):
            mn = (codes_c.shape[0], codes_c.shape[1] + codes_r.shape[1])
            codes = ("level", codes, codes_c, codes_r, mn)
        return codes, {"shape": data.shape}

    def decode(self, codes, aux, eps):
        pairs = []
        while codes[0] == "level":
            _, codes, codes_c, codes_r, _ = codes
            pairs.append((codes_c, codes_r))
        return _interp_decode(codes[1], pairs, eps)

    def size_bytes(self, codes, aux, eps):
        if codes[0] == "root":
            return lossless.coded_size_bytes(np.asarray(codes[1]))
        _, sub_codes, codes_c, codes_r, _ = codes
        return (self.size_bytes(sub_codes, aux, eps)
                + lossless.coded_size_bytes(np.asarray(codes_c))
                + lossless.coded_size_bytes(np.asarray(codes_r)))


@base.compiled("sz2")
def _sz2_encode(data, eps):
    ndim = data.ndim
    blocks = _to_blocks(_pad_to_multiple(data, BLOCK), BLOCK)
    # Lorenzo path (per block, dual quantization)
    lor_codes = _block_lorenzo_codes(quantize_bounded(blocks, eps))
    # Regression path
    cq = _quantize_coefs(_fit_planes(blocks), eps)
    reg_codes = quantize_bounded(blocks - _plane_values(cq, eps, ndim), eps)
    # Choice: smaller |codes| mass (entropy proxy); regression also pays
    # for its coefficients (~ (ndim+1)*2 bytes -> ~ 8 code units).
    axes = tuple(range(1, ndim + 1))
    lor_cost = jnp.sum(jnp.minimum(jnp.abs(lor_codes), 255), axis=axes)
    reg_cost = jnp.sum(jnp.minimum(jnp.abs(reg_codes), 255), axis=axes) + 4 * (ndim + 1)
    use_reg = reg_cost < lor_cost
    sel = jnp.where(use_reg[(...,) + (None,) * ndim], reg_codes, lor_codes)
    return sel, use_reg, cq


@base.compiled("sz2", static=("shape",))
def _sz2_decode(codes, use_reg, cq, eps, *, shape):
    ndim = len(shape)
    reg_blocks = _plane_values(cq, eps, ndim) + dequantize(codes, eps)
    lor_blocks = dequantize(_block_lorenzo_decode(codes), eps)
    blocks = jnp.where(use_reg[(...,) + (None,) * ndim], reg_blocks, lor_blocks)
    full = _from_blocks(blocks, _padded_shape(shape, BLOCK), BLOCK)
    return full[tuple(slice(0, s) for s in shape)]


class SZ2(base.Compressor):
    """SZ2: dynamic per-block selection between Lorenzo and regression.

    Mirrors SZ2's sampling-based scheme choice: per block, both predictors
    are evaluated and the one with the smaller absolute residual mass (a
    monotone proxy for the coded entropy) wins.  One flag bit per block.
    """
    name = "sz2"

    def encode(self, data, eps):
        sel, use_reg, cq = _sz2_encode(data, eps)
        return sel, {
            "shape": data.shape, "padded": _padded_shape(data.shape, BLOCK),
            "use_reg": use_reg, "coef_codes": cq,
        }

    def decode(self, codes, aux, eps):
        return _sz2_decode(codes, aux["use_reg"], aux["coef_codes"], eps,
                           shape=tuple(aux["shape"]))

    def size_bytes(self, codes, aux, eps):
        total = lossless.coded_size_bytes(np.asarray(codes))
        use_reg = np.asarray(aux["use_reg"])
        total += int(np.ceil(use_reg.size / 8))  # 1 flag bit / block
        cq = np.asarray(aux["coef_codes"])[use_reg]  # only coded when chosen
        if cq.size:
            total += lossless.coded_size_bytes(cq)
        return total

    def regression_fraction(self, data, eps) -> float:
        """Fraction of blocks choosing regression (paper section 4.2 stat)."""
        _, aux = self.encode(data, eps)
        return float(jnp.mean(aux["use_reg"].astype(jnp.float32)))


base.register(SZLorenzo())
base.register(SZRegression())
base.register(SZInterp())
base.register(SZ2())
