"""jnp reference route for the fused quality sweep.

Computes, for a stack of slices and a grid of error bounds, the sum of
squared quantize-dequantize errors -- the one data-dependent reduction
behind PSNR and NRMSE of the quantization proxy.  Everything here is
written so the batched reference and the per-slice Pallas kernel produce
BITWISE identical f32 results:

- the only reduction is an explicit balanced elementwise tree
  (``tile_sse``), never ``jnp.sum`` -- XLA is free to reshape a generic
  reduction's tree with the batch shape, elementwise adds it is not;
- tiles accumulate sequentially in the same order as the kernel's grid
  (last grid dimension fastest), as a plain ``+=`` chain;
- padding is with 0.0: the QDQ error of 0.0 is exactly 0.0 for every
  eps, and adding +0.0 to a (>= +0.0) f32 accumulator is a bitwise
  no-op, so padded and unpadded streams agree bit for bit.

No Pallas imports here: this module is the oracle the kernel is checked
against, and it must load on environments without pallas support.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import f32, quant

# The tile is part of the numerical spec, not a tuning knob: SSE partial
# sums depend on the accumulation boundaries, so every route (reference,
# kernel, sharded, streamed, served) must use the same tile.  8 sublanes
# x 256 lanes; the lane count must be a power of two for the halving
# tree in ``tile_sse``.
DEFAULT_TILE = 2048

# PSNR is clamped to +-PSNR_CAP dB.  An exactly-representable slice
# (SSE == 0, "infinite" PSNR) reports +PSNR_CAP; a zero-range slice with
# nonzero error reports -PSNR_CAP.  300 dB sits far above the ~200 dB
# ceiling int32 quantization can express, so no real measurement clips.
PSNR_CAP = 300.0

# NRMSE of a zero-range slice with nonzero error would be +inf; the cap
# keeps every emitted tensor finite (tests assert no NaN/inf anywhere).
NRMSE_CAP = 1e30


def qdq_error_sq(x, eps):
    """Elementwise squared quantize-dequantize error at ``eps``.

    Same saturating uniform quantizer as the predictor stack
    (``repro.quant``): codes are ``floor(x / eps)`` clipped to the int32
    range, dequantized as ``code * eps``.
    """
    err = x - quant.codes(x, eps).astype(jnp.float32) * eps
    return err * err


def tile_sse(err2):
    """Reduce a (..., 8, c) squared-error tile to (...) with a FIXED
    balanced tree of elementwise adds (c must be a power of two).

    The 8 sublanes fold as explicit pairs, then the lane axis halves
    until scalar.  Elementwise adds are bit-deterministic per element
    regardless of leading batch dims, which is what makes the batched
    reference bit-equal to the per-slice kernel.
    """
    s = err2
    v = (((s[..., 0, :] + s[..., 1, :]) + (s[..., 2, :] + s[..., 3, :]))
         + ((s[..., 4, :] + s[..., 5, :]) + (s[..., 6, :] + s[..., 7, :])))
    while v.shape[-1] > 1:
        v = v[..., 0::2] + v[..., 1::2]
    return v[..., 0]


def tile_sse_all_eps(xt, epss, n_eps):
    """One (..., 8, c) tile -> (..., n_eps) SSE, one eps at a time."""
    return jnp.stack([tile_sse(qdq_error_sq(xt, epss[ei]))
                      for ei in range(n_eps)], axis=-1)


def sse_sweep(xb, epss, tile):
    """(k, 8, n/8) tiled layout x (e,) -> (k, e) f32 SSE, reference route.

    ``xb`` is the kernel's input layout: the zero-padded flat slice
    reshaped (k, n/8, 8) and swapped to (k, 8, n/8), so element i sits
    at sublane i % 8, column i // 8, and tile t covers the contiguous
    elements [t*tile, (t+1)*tile).  Tiles accumulate sequentially --
    the same order as the kernel's (k, T) grid with T fastest -- in a
    ``lax.scan``, so the program stays one tile long however many tiles
    a slice or volume has.
    """
    n_eps = int(epss.shape[0])
    c = tile // 8
    k, _, w = xb.shape
    tiles = jnp.moveaxis(xb.reshape(k, 8, w // c, c), 2, 0)   # (T, k, 8, c)

    def step(acc, xt):
        return acc + tile_sse_all_eps(xt, epss, n_eps), None

    acc, _ = jax.lax.scan(step, jnp.zeros((k, n_eps), jnp.float32), tiles)
    return acc


_LOG10_2 = 0.30102999566398120


def det_log10(x):
    """Bit-deterministic elementwise log10 for positive f32 inputs.

    Library ``log`` implementations are NOT batch-shape-invariant on
    CPU -- the SIMD main loop and the scalar remainder round differently,
    so the same element changes bits when its array length changes
    (exactly what sharding does) -- and the TPU's is off by ~1e-4
    relative.  ``f32.log2`` uses only bitcasts, +, * and a correctly
    rounded division, so its bits never move with the batch shape or
    the backend.  x <= 0 maps to -1e4, which the PSNR clip floors out
    exactly like the -inf a true log would give.  XLA CPU runs with
    denormals-are-zero, so subnormal inputs take the same -1e4 branch --
    a subnormal data range degrades to the clip caps, deterministically,
    on every route.
    """
    x = x.astype(jnp.float32)
    return jnp.where(x > 0.0, jnp.float32(_LOG10_2) * f32.log2(x),
                     jnp.float32(-1e4))


def quality_from_stats(sse, n, vmin, vmax):
    """(k, e) SSE + per-slice stats -> (k, e, 2) [PSNR dB, NRMSE].

    ``n`` is the UNPADDED element count and ``vmin``/``vmax`` the
    unpadded per-slice extrema (shared by every route).  ``abs`` on the
    range kills the -0.0 hazard: on a mixed-sign-zero slice min/max may
    tie-break either way, and a -0.0 range would send NRMSE to -inf on
    one route and +inf on another.
    """
    rng = jnp.abs(vmax - vmin)[:, None]                      # (k, 1)
    mse = f32.div(sse, jnp.float32(n))
    exact = sse == 0.0
    psnr = jnp.where(
        exact, jnp.float32(PSNR_CAP),
        jnp.clip(20.0 * det_log10(rng) - 10.0 * det_log10(mse),
                 -PSNR_CAP, PSNR_CAP))
    nrmse = jnp.where(
        exact, jnp.float32(0.0),
        jnp.clip(f32.div(jnp.sqrt(mse), rng), 0.0, jnp.float32(NRMSE_CAP)))
    return jnp.stack([psnr, nrmse], axis=-1)
