"""Shared quantization plumbing: the codes, their saturation, eps validation.

One home for the constants and checks that BOTH quantization routes (the
jnp sort/histogram paths in ``repro.core.predictors`` and the Pallas
kernel route in ``repro.kernels.qent``) must agree on exactly -- the
sharded-equivalence gates depend on the routes staying bit-identical.
Imports nothing from core/kernels/dist.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro import f32

# floor(x/eps) is clamped to this f32-representable sub-range of int32
# before any cast: the largest float32 not exceeding 2^31 - 1 is
# 2147483520.0, so casting the clamped value can never wrap (a wrapped
# code would corrupt run-length / histogram entropies).
INT32_CODE_MIN = -2147483648.0
INT32_CODE_MAX = 2147483520.0


def codes(x, eps):
    """``floor(x / eps)`` as int32, the quotient rounded as IEEE f32
    division rounds it on every backend, clamped before the cast."""
    q = jnp.floor(f32.div(x, eps))
    return jnp.clip(q, INT32_CODE_MIN, INT32_CODE_MAX).astype(jnp.int32)


def validate_eps_positive(epss) -> None:
    """Reject non-positive / non-finite error bounds at trace boundaries.

    Only applies to concrete values: inside jit the caller's public entry
    point has already validated (tracers carry no values to check).  The
    check runs in numpy -- it sits on per-probe hot paths (UC1 bisection),
    where a jnp check would add a device dispatch + host sync per call.
    """
    # tree_leaves catches tracers however they arrive: bare, or wrapped
    # in a list/tuple like the engine's features(slices, eps) -> [eps]
    if any(isinstance(leaf, jax.core.Tracer)
           for leaf in jax.tree_util.tree_leaves(epss)):
        return
    arr = np.asarray(epss)
    if arr.size and not bool(np.all(np.isfinite(arr) & (arr > 0))):
        raise ValueError(
            f"error bounds must be positive and finite, got {arr}; "
            "an eps <= 0 makes floor(x/eps) ill-defined")
