"""Tiled Gram-matrix Pallas kernel: G = X^T X (or X X^T).

The SVD-trunc predictor needs only singular *values*; on TPU we get them
from ``eigvalsh`` of the Gram matrix, turning the predictor's hot loop into
one MXU-resident matmul.  Classic three-loop tiling: grid = (n/bn, n/bn,
m/bk) with accumulation over the contraction tiles; 128-aligned blocks to
match the MXU systolic array.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import backend


DEFAULT_BN = 128   # output tile edge (MXU-aligned)
DEFAULT_BK = 128   # contraction tile


def _gram_kernel(x1_ref, x2_ref, o_ref):
    """One (bn, bn) output tile; accumulates over the k grid dimension."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    a = x1_ref[...]            # (bk, bn) tile of X[:, i-block]
    b = x2_ref[...]            # (bk, bn) tile of X[:, j-block]
    o_ref[...] += jax.lax.dot_general(
        a, b, (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,   # f32 passes, not bf16
        preferred_element_type=jnp.float32,
    )


@functools.partial(jax.jit, static_argnames=("bn", "bk"))
def gram_xtx(x: jnp.ndarray, bn: int = DEFAULT_BN, bk: int = DEFAULT_BK) -> jnp.ndarray:
    """X^T X for (m, n) x, m % bk == 0 and n % bn == 0 (pad in ops.py)."""
    m, n = x.shape
    assert m % bk == 0 and n % bn == 0, (m, n, bk, bn)
    grid = (n // bn, n // bn, m // bk)
    return pl.pallas_call(
        _gram_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, i)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bn, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
        interpret=backend.interpret(),
        name="gram",
    )(x, x)


def _gram_batched_kernel(x1_ref, x2_ref, o_ref):
    """One (bn, bn) output tile of one slice; grid = (k, n/bn, n/bn, m/bk).

    The slice index is the *leading* grid dimension, so the whole stack of
    Gram matrices runs as a single MXU-resident launch: the accumulator
    tile stays in VMEM across the contraction steps of each slice and the
    k separate kernel launches of the unbatched path collapse into one.
    """
    @pl.when(pl.program_id(3) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    a = x1_ref[0]              # (bk, bn) tile of X[s, :, i-block]
    b = x2_ref[0]              # (bk, bn) tile of X[s, :, j-block]
    o_ref[0] += jax.lax.dot_general(
        a, b, (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,   # f32 passes, not bf16
        preferred_element_type=jnp.float32,
    )


@functools.partial(jax.jit, static_argnames=("bn", "bk"))
def gram_xtx_batched(x: jnp.ndarray, bn: int = DEFAULT_BN,
                     bk: int = DEFAULT_BK) -> jnp.ndarray:
    """Batched X^T X: (k, m, n) -> (k, n, n), one launch for all k slices."""
    k, m, n = x.shape
    assert m % bk == 0 and n % bn == 0, (k, m, n, bk, bn)
    grid = (k, n // bn, n // bn, m // bk)
    return pl.pallas_call(
        _gram_batched_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bk, bn), lambda s, i, j, t: (s, t, i)),
            pl.BlockSpec((1, bk, bn), lambda s, i, j, t: (s, t, j)),
        ],
        out_specs=pl.BlockSpec((1, bn, bn), lambda s, i, j, t: (s, i, j)),
        out_shape=jax.ShapeDtypeStruct((k, n, n), jnp.float32),
        interpret=backend.interpret(),
        name="gram_batched",
    )(x, x)
