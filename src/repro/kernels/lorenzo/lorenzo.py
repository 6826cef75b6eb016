"""Dual-quantization Lorenzo Pallas kernel (cuSZ reformulation, 2-D).

codes[i,j] = Q(x[i,j]) - Q(x[i-1,j]) - Q(x[i,j-1]) + Q(x[i-1,j-1])

where Q is the bounded quantizer.  Classic SZ is sequential (predicts from
reconstructed values); dual quantization pre-quantizes every element, making
the stencil embarrassingly parallel.  The cross-tile halo is handled with
the recompute-over-communicate idiom: the kernel receives four shifted views
of the zero-padded input (four overlapping HBM->VMEM streams of the same
buffer) and re-quantizes each -- redundant VPU flops instead of
neighbour-tile synchronization, the right trade on TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compressors.sz import quantize_bounded as _quantize
from repro.kernels import backend

DEFAULT_BM = 256
DEFAULT_BN = 256


def _lorenzo_kernel(eps_ref, a_ref, b_ref, c_ref, d_ref, o_ref):
    eps = eps_ref[0]
    qa = _quantize(a_ref[...], eps)   # x[i, j]
    qb = _quantize(b_ref[...], eps)   # x[i-1, j]
    qc = _quantize(c_ref[...], eps)   # x[i, j-1]
    qd = _quantize(d_ref[...], eps)   # x[i-1, j-1]
    o_ref[...] = qa - qb - qc + qd


@functools.partial(jax.jit, static_argnames=("bm", "bn"))
def lorenzo2d(
    x: jnp.ndarray,
    eps: jnp.ndarray,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
) -> jnp.ndarray:
    """2-D Lorenzo codes; x shape (m, n) with m % bm == 0, n % bn == 0."""
    m, n = x.shape
    assert m % bm == 0 and n % bn == 0, (x.shape, bm, bn)
    xp = jnp.pad(x.astype(jnp.float32), ((1, 0), (1, 0)))
    a = xp[1:, 1:]
    b = xp[:-1, 1:]
    c = xp[1:, :-1]
    d = xp[:-1, :-1]
    eps_arr = jnp.asarray([eps], jnp.float32)
    spec = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
    return pl.pallas_call(
        _lorenzo_kernel,
        grid=(m // bm, n // bn),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), spec, spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=backend.interpret(),
    )(eps_arr, a, b, c, d)
