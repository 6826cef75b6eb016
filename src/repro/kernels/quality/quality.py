"""Fused quantize-dequantize SSE Pallas kernel (the quality sweep).

One launch computes the sum of squared QDQ errors for a (k, n) stack of
flattened slices x an (e,) vector of error bounds: each input tile is
read from HBM once and quantize-dequantized at every error bound while
resident in VMEM, exactly like ``kernels/qent``.  The reduction inside a
tile is the fixed balanced tree of ``ref.tile_sse``, and tiles
accumulate across the sequential TPU grid in the same order as
``ref.sse_sweep``'s scan -- that pairing is what makes the kernel route
BITWISE equal to the jnp reference.

The TPU has no strided lane gather, so the kernel walks the same tree
with lane rotations: after ``v += roll(v, -h)`` for h = 1, 2, 4, ...
lane 0 holds ``ref.tile_sse``'s sum with every add associated exactly
as there.  The other lanes hold partial sums nobody reads; the (1, c)
row is accumulated whole and lane 0 is taken outside the kernel.

Unlike qent there is no eb blocking: the per-eps live tile is a single
(8, tile/8) f32 block (8 KB at the default tile), nowhere near VMEM
limits, and the tile size is part of the numerical spec (accumulation
boundaries move with it), so it must never silently shrink per backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import backend
from repro.kernels.quality import ref as _ref

DEFAULT_TILE = _ref.DEFAULT_TILE


def _tile_sse_lane0(err2):
    """(8, c) squared errors -> (1, c) row whose lane 0 is
    ``ref.tile_sse(err2)`` bit for bit (same sublane pairs, then the
    same adjacent-lane pairs, level by level)."""
    s = err2
    v = (((s[0:1] + s[1:2]) + (s[2:3] + s[3:4]))
         + ((s[4:5] + s[5:6]) + (s[6:7] + s[7:8])))
    c = v.shape[1]
    h = 1
    while h < c:                       # v[i] += v[i + h]
        v = v + pltpu.roll(v, c - h, 1)
        h *= 2
    return v


def _quality_sweep_kernel(eps_ref, x_ref, sse_ref, *, n_eps: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        sse_ref[...] = jnp.zeros_like(sse_ref)

    x = x_ref[0]                                     # (8, tile/8): ONE read
    for ei in range(n_eps):
        sse_ref[0, ei] += _tile_sse_lane0(_ref.qdq_error_sq(x, eps_ref[ei]))


@functools.partial(jax.jit, static_argnames=("tile",))
def qdq_sse_sweep(xb: jnp.ndarray, epss: jnp.ndarray,
                  tile: int = DEFAULT_TILE) -> jnp.ndarray:
    """(k, 8, n/8) tiled stack x (e,) error bounds -> (k, e) f32 SSE.

    ``xb`` is the shared layout produced by ``ops.quality_sweep`` (flat
    slices zero-padded to a tile multiple, reshaped (k, n/8, 8), axes
    1/2 swapped).  Grid = (k slices, n/tile tiles), SSE accumulates in
    the output ref across the sequential grid.
    """
    k = xb.shape[0]
    c = tile // 8
    n = xb.shape[2] * 8
    (n_eps,) = epss.shape
    assert n % tile == 0, (n, tile)
    kernel = functools.partial(_quality_sweep_kernel, n_eps=n_eps)
    sse = pl.pallas_call(
        kernel,
        grid=(k, n // tile),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 8, c), lambda s, t: (s, 0, t)),
        ],
        out_specs=pl.BlockSpec((1, n_eps, 1, c), lambda s, t: (s, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((k, n_eps, 1, c), jnp.float32),
        interpret=backend.interpret(),
        name="quality_sse",
    )(jnp.asarray(epss, jnp.float32), xb)
    return sse[:, :, 0, 0]
