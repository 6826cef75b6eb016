"""f32 division and logarithms that give the same bits on every backend.

The TPU divides by multiplying with an approximate reciprocal: its
``x / y`` may be one or two ulps off the IEEE quotient the CPU returns,
which moves ``floor(x / eps)`` wherever the quotient sits next to an
integer, and its ``log`` is off by up to ~1e-4 relative.  Products and
sums are IEEE round-to-nearest on both, so the helpers here build on
those (and on bitcasts) only.  On the CPU :func:`div` returns exactly
``x / y``.

Leaf module: imports nothing from the rest of the package.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# Beyond 2**31 a quantizer code saturates (``repro.quant``), so the last
# bits of a larger quotient never matter; skipping it also keeps the
# products below from overflowing.
_REFINE_BELOW = 2.0 ** 31

_INV_LN2 = 1.4426950408889634
_LN2 = 0.6931471805599453


def _split(a):
    """``a = hi + lo`` exactly, each with at most 12 significant bits
    (``hi`` is ``a`` with the low 12 mantissa bits cleared)."""
    bits = jax.lax.bitcast_convert_type(a, jnp.int32)
    hi = jax.lax.bitcast_convert_type(bits & ~0xFFF, jnp.float32)
    return hi, a - hi


def _residual(x, q, y):
    """``x - q * y`` for ``q`` near ``x / y``, to ~2**-34 of ``x``: far
    finer than the ~2**-24 steps between the residuals of neighbouring
    ``q``.  Each partial product of 12-bit halves is exact, so a
    compiler that fuses a product into an FMA changes nothing, and
    ``x - qh * yh`` is exact (its operands are within a factor of 2)."""
    qh, ql = _split(q)
    yh, yl = _split(y)
    return (((x - qh * yh) - qh * yl) - ql * yh) - ql * yl


def _neighbours(q):
    """The f32 values next to ``q`` on either side (for ``q != 0``)."""
    b = jax.lax.bitcast_convert_type(q, jnp.int32)
    step = jnp.where(b >= 0, 1, -1)
    return (jax.lax.bitcast_convert_type(b - step, jnp.float32),
            jax.lax.bitcast_convert_type(b + step, jnp.float32))


def refine_quotient(x, y, q):
    """The IEEE round-to-nearest quotient ``x / y`` (``y > 0``), from an
    approximation ``q`` a few ulps from it.

    One Newton step on the exact residual brings ``q`` within an ulp;
    then whichever of ``q`` and its two neighbours leaves the smallest
    residual is the correctly rounded quotient (an f32 quotient of two
    f32 values is never exactly halfway between two f32 values).
    Quotients of magnitude 2**31 and above pass through as they are."""
    # f32 operands (a Python float would split and multiply in f64) of
    # one shape (Mosaic bitcasts vectors only, not a scalar eps)
    x, y = jnp.broadcast_arrays(jnp.asarray(x, jnp.float32),
                                jnp.asarray(y, jnp.float32))
    # a zero quotient is exact; inf and nan fail the first test
    ok = (jnp.abs(q) < _REFINE_BELOW) & (q != 0.0)
    q0 = jnp.where(ok, q, 1.0)
    q0 = q0 + _residual(x, q0, y) / y
    best, r = q0, jnp.abs(_residual(x, q0, y))
    for c in _neighbours(q0):
        rc = jnp.abs(_residual(x, c, y))
        best = jnp.where(rc < r, c, best)
        r = jnp.minimum(rc, r)
    return jnp.where(ok, best, q)


def div(x, y):
    """``x / y`` rounded to nearest as IEEE f32 prescribes, for ``y > 0``
    and quotients below 2**31 in magnitude."""
    x, y = jnp.asarray(x, jnp.float32), jnp.asarray(y, jnp.float32)
    return refine_quotient(x, y, x / y)


def log2(x):
    """log2 of positive f32 ``x`` to a few ulps, the same bits everywhere.

    Split x = m * 2**e with m in [1, 2) via the f32 bit layout
    (subnormals pre-scaled by 2**64), then log2(m) from the atanh series
    in t = (m-1)/(m+1) (|t| <= 1/3: the t**15 tail is < 1e-8, below f32
    resolution).  Backends that flush subnormals see them as 0, and
    ``x <= 0`` gives a meaningless finite value: callers mask both."""
    x = x.astype(jnp.float32)
    small = x < 2.0 ** -100
    xs = jnp.where(small, x * jnp.float32(2.0 ** 64), x)
    bits = jax.lax.bitcast_convert_type(xs, jnp.int32)
    e = ((bits >> 23) & 0xFF) - 127
    m = jax.lax.bitcast_convert_type(
        (bits & 0x007FFFFF) | (127 << 23), jnp.float32)
    t = div(m - 1.0, m + 1.0)
    s = t * t
    p = jnp.float32(1.0 / 13.0)
    for c in (1.0 / 11.0, 1.0 / 9.0, 1.0 / 7.0, 1.0 / 5.0, 1.0 / 3.0, 1.0):
        p = p * s + jnp.float32(c)
    log2m = (2.0 * _INV_LN2) * (t * p)
    return e.astype(jnp.float32) + log2m - jnp.where(small, 64.0, 0.0)


def log(x):
    """Natural log of positive f32 ``x`` from :func:`log2`, the same bits
    everywhere (within a few ulps of the true value)."""
    return log2(x) * jnp.float32(_LN2)
