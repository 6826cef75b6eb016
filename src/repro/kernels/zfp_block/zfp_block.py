"""ZFP forward-transform Pallas kernel (2-D, 4x4 blocks).

Per 4x4 block: block-floating-point alignment to the block's max exponent,
then the exact zfp integer lifting along rows and columns.  A (BM, BN) VMEM
tile holds (BM/4) x (BN/4) blocks; the lifting is expressed as strided
slices of the tile so all blocks advance in lockstep on the VPU (no 4-wide
vectors: lanes stay 128-wide).

Outputs: transformed int32 coefficients (same layout) + per-block exponents.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import backend

INTPREC = 26
DEFAULT_BM = 128
DEFAULT_BN = 128


def _lift_rows(q: jnp.ndarray) -> jnp.ndarray:
    """Lift along axis 0 within each 4-row group: q is (BM, BN) int32."""
    x, y, z, w = q[0::4], q[1::4], q[2::4], q[3::4]
    x = x + w; x = x >> 1; w = w - x
    z = z + y; z = z >> 1; y = y - z
    x = x + z; x = x >> 1; z = z - x
    w = w + y; w = w >> 1; y = y - w
    w = w + (y >> 1); y = y - (w >> 1)
    bm, bn = q.shape
    out = jnp.zeros_like(q)
    out = out.at[0::4].set(x).at[1::4].set(y).at[2::4].set(z).at[3::4].set(w)
    return out


def _lift_cols(q: jnp.ndarray) -> jnp.ndarray:
    x, y, z, w = q[:, 0::4], q[:, 1::4], q[:, 2::4], q[:, 3::4]
    x = x + w; x = x >> 1; w = w - x
    z = z + y; z = z >> 1; y = y - z
    x = x + z; x = x >> 1; z = z - x
    w = w + y; w = w >> 1; y = y - w
    w = w + (y >> 1); y = y - (w >> 1)
    out = jnp.zeros_like(q)
    out = (out.at[:, 0::4].set(x).at[:, 1::4].set(y)
              .at[:, 2::4].set(z).at[:, 3::4].set(w))
    return out


def _block_exponents(x: jnp.ndarray) -> jnp.ndarray:
    """(BM, BN) -> (BM/4, BN/4) ceil-log2 max-abs exponent per 4x4 block."""
    bm, bn = x.shape
    a = jnp.abs(x).reshape(bm // 4, 4, bn // 4, 4)
    amax = jnp.max(a, axis=(1, 3))
    e = jnp.ceil(jnp.log2(jnp.maximum(amax, 1e-38))).astype(jnp.int32)
    return jnp.where(amax > 0, e, 0)


def _zfp_kernel(x_ref, coef_ref, exp_ref):
    x = x_ref[...].astype(jnp.float32)
    e = _block_exponents(x)                                  # (BM/4, BN/4)
    scale = jnp.exp2((INTPREC - 2 - e).astype(jnp.float32))
    scale_full = jnp.repeat(jnp.repeat(scale, 4, axis=0), 4, axis=1)
    q = jnp.round(x * scale_full).astype(jnp.int32)
    q = _lift_rows(q)
    q = _lift_cols(q)
    coef_ref[...] = q
    exp_ref[...] = e


@functools.partial(jax.jit, static_argnames=("bm", "bn"))
def zfp_forward2d(x: jnp.ndarray, bm: int = DEFAULT_BM, bn: int = DEFAULT_BN):
    """(m, n) -> (coeffs int32 (m, n), exponents int32 (m/4, n/4)).

    m % bm == 0, n % bn == 0 and bm, bn % 4 == 0 (ops.py pads).
    """
    m, n = x.shape
    assert m % bm == 0 and n % bn == 0
    return pl.pallas_call(
        _zfp_kernel,
        grid=(m // bm, n // bn),
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bm // 4, bn // 4), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), jnp.int32),
            jax.ShapeDtypeStruct((m // 4, n // 4), jnp.int32),
        ],
        interpret=backend.interpret(),
    )(x)
