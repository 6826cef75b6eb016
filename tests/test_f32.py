"""Backend-independent f32 division and log2 (``repro.f32``)."""
import numpy as np
import jax
import pytest

from repro import f32, quant

RNG = np.random.default_rng(0)
X = (RNG.standard_normal(100_000)
     * 10.0 ** RNG.uniform(-4, 4, 100_000)).astype(np.float32)


def _step(q, direction, times):
    for _ in range(times):
        q = np.nextafter(q, np.float32(direction))
    return q


@pytest.mark.parametrize("y", [1e-5, 5.716e-5, 3.3e-4, 0.0123, 7.7])
@pytest.mark.parametrize("ulps", [-2, -1, 1, 2])
def test_refine_quotient_recovers_ieee_division(y, ulps):
    """A quotient off by up to two ulps, as the TPU's reciprocal-based
    division returns it, is refined to the IEEE quotient bit for bit."""
    y = np.float32(y)
    want = X / y
    approx = _step(X * (np.float32(1) / y), np.inf if ulps > 0 else -np.inf,
                   abs(ulps))
    got = np.asarray(jax.jit(f32.refine_quotient)(X, y, approx))
    big = np.abs(approx) >= 2.0 ** 31              # saturating codes
    assert np.array_equal(got[~big].view(np.int32), want[~big].view(np.int32))
    assert np.array_equal(got[big], approx[big])


def test_div_and_codes_match_numpy():
    for y in (np.float32(1e-5), np.float32(0.37)):
        q = np.asarray(jax.jit(f32.div)(X, y))
        assert np.array_equal(q.view(np.int32), (X / y).view(np.int32))
        codes = np.asarray(jax.jit(quant.codes)(X, y))
        want = np.clip(np.floor(X / y), quant.INT32_CODE_MIN,
                       quant.INT32_CODE_MAX).astype(np.int64)
        assert np.array_equal(codes, want)


def test_div_of_python_floats_is_f32():
    got = np.asarray(f32.div(X, 1e-5 * 3.0))
    assert np.array_equal(got, X / np.float32(1e-5 * 3.0))


def test_div_passes_zero_and_saturating_quotients_through():
    x = np.array([0.0, -0.0, 3e38, -3e38, 1e30], np.float32)
    y = np.float32(1e-5)
    got = np.asarray(jax.jit(f32.div)(x, y))
    with np.errstate(over="ignore"):
        want = x / y
    assert np.array_equal(got.view(np.int32), want.view(np.int32))


@pytest.mark.parametrize("name", ["log2", "log"])
def test_logs_within_a_few_ulps(name):
    x = np.concatenate([
        np.arange(1, 100_000, dtype=np.float32),
        (RNG.random(100_000) * 10.0 ** RNG.uniform(-30, 30, 100_000)
         ).astype(np.float32),
        np.float32(1) - np.arange(1, 1000, dtype=np.float32) * 2**-24])
    got = np.asarray(jax.jit(getattr(f32, name))(x)).astype(np.float64)
    want = getattr(np, name)(x.astype(np.float64))
    unit = np.spacing(np.maximum(np.abs(want), 1.0).astype(np.float32))
    assert np.max(np.abs(got - want) / unit) <= 4.0
    assert np.array_equal(got[:1], [0.0])
