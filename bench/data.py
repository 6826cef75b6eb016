"""Field stacks made on the device from the seed, in one jitted call.

The generators are copies of the program's synthetic stand-ins for the
SDRBench fields (``miranda_like``, ``hurricane_like``), kept here so the
benchmark's data cannot change with the program.  Each field gets its
own key from its name and the run's seed; the slices of a field vary
smoothly along the stack (parameter ``z`` from 0 to pi), as the
program's ``field_slices`` makes them, but all slices of all fields are
made in one program instead of one slice at a time.
"""
from __future__ import annotations

import zlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def _fbm_spectrum_field(key, n: int, slope):
    """Power-law (turbulence-like) random field: |k|^-slope spectrum."""
    freq = jnp.fft.fftfreq(n) * n
    k2 = freq[:, None] ** 2 + freq[None, :] ** 2
    spec = jnp.where(k2 > 0, k2 ** (-slope / 2.0), 0.0)
    kr, ki = jax.random.split(key)
    noise = jax.random.normal(kr, (n, n)) + 1j * jax.random.normal(ki, (n, n))
    f = jnp.fft.ifft2(noise * jnp.sqrt(spec)).real
    return f / jnp.maximum(jnp.std(f), 1e-9)


def miranda_like(key, n: int, z):
    """Multicomponent-flow field: smooth turbulence plus a sharp material
    interface whose position drifts with the slice parameter ``z``."""
    k1, _ = jax.random.split(key)
    mix = 0.5 - 0.5 * jnp.cos(z)
    turb = _fbm_spectrum_field(k1, n, slope=4.0 - 1.8 * mix)
    ii = jnp.linspace(-1, 1, n)
    front = jnp.tanh((ii[:, None] - 0.3 * jnp.sin(3 * z) +
                      (0.05 + 0.4 * mix) * turb) * (2.0 + 12.0 * mix))
    return (1.5 + 0.5 * front + (0.05 + 0.45 * mix) * turb).astype(jnp.float32)


def hurricane_like(key, n: int, z):
    """Wind component with a vortex: solid-body core, 1/r tail, noise."""
    k1, _ = jax.random.split(key)
    ii = jnp.linspace(-1, 1, n)
    x, y = jnp.meshgrid(ii, ii, indexing="ij")
    cx, cy = 0.25 * jnp.sin(z), 0.25 * jnp.cos(z)
    r = jnp.sqrt((x - cx) ** 2 + (y - cy) ** 2) + 1e-3
    vtheta = jnp.where(r < 0.2, r / 0.2, 0.2 / r) * 40.0
    u = -vtheta * (y - cy) / r
    mix = 0.5 - 0.5 * jnp.cos(z)
    noise = (0.5 + 6.0 * mix) * _fbm_spectrum_field(k1, n,
                                                    slope=3.6 - 1.4 * mix)
    return (u + noise).astype(jnp.float32)


GENERATORS = {"miranda_like": miranda_like, "hurricane_like": hurricane_like}


def field_words(fields, seed: int) -> np.ndarray:
    """(F, 3) uint32 words that key each field: its name's CRC and the
    two 32-bit halves of the seed (a seed may exceed 32 bits)."""
    s = int(seed) % (1 << 64)
    return np.asarray([[zlib.crc32(f.encode()), s & 0xFFFFFFFF, s >> 32]
                       for f in fields], np.uint32)


@partial(jax.jit, static_argnames=("generator", "count", "n"))
def _make(words, *, generator: str, count: int, n: int):
    gen = GENERATORS[generator]
    zs = jnp.linspace(0.0, jnp.pi, count)

    def one_field(w):
        key = jax.random.PRNGKey(w[0])
        key = jax.random.fold_in(jax.random.fold_in(key, w[1]), w[2])
        keys = jax.random.split(key, count)
        x = jax.vmap(lambda k, z: gen(k, n, z))(keys, zs)
        return x, jnp.min(x), jnp.max(x)

    return jax.lax.map(one_field, words)


def make_fields(fields, seed: int, *, generator: str, count: int, n: int):
    """One (count, n, n) f32 stack per field name and the per-field
    (min, max) on the host: ``(stacks, lo, hi)``."""
    stacked, lo, hi = _make(jnp.asarray(field_words(fields, seed)),
                            generator=generator, count=count, n=n)
    stacks = [stacked[i] for i in range(len(fields))]
    jax.block_until_ready(stacks)
    del stacked
    return stacks, np.asarray(lo, np.float64), np.asarray(hi, np.float64)


@jax.jit
def _take(x, order):
    return x[order]


def shuffle_slices(stacks: list, seed: int) -> list:
    """The same slices of each stack, in an order drawn from the seed,
    in place: every seed gives the same work, in another order."""
    rng = np.random.default_rng((int(seed) % (1 << 64), 1))
    for i, x in enumerate(stacks):
        stacks[i] = _take(x, jnp.asarray(rng.permutation(x.shape[0])))
        del x
    jax.block_until_ready(stacks)
    return stacks


def eb_grid(eps: float, lo: float, hi: float, n_ebs: int,
            top: float) -> np.ndarray:
    """``n_ebs`` error bounds, geometric from ``eps`` to ``top`` of the
    field's value range, in f32."""
    return np.geomspace(eps, top * (hi - lo), n_ebs).astype(np.float32)
