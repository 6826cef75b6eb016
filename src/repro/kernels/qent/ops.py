"""jit'd public wrappers for the qent kernel (padding + entropy reduction)."""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.kernels import tune as _tune
from repro.kernels.qent import qent as _k
from repro.kernels.qent import ref as _ref
from repro import quant
from repro.quant import validate_eps_positive as _check_eps


def quantized_entropy_sweep(
    x: jnp.ndarray,
    epss: jnp.ndarray,
    num_bins: int = _k.DEFAULT_BINS,
    *,
    tile: Optional[int] = None,
    tune: Optional[_tune.TuneConfig] = None,
) -> jnp.ndarray:
    """Entropies for a stack of slices at a vector of error bounds.

    ``x``: (k, ...) stack (trailing dims flattened per slice);
    ``epss``: (e,).  Returns (k, e) bits/symbol from one fused kernel
    launch that reads each input tile once (eb vectors wider than
    ``qent.max_eps_per_launch(num_bins)`` take one launch per block of
    that many ebs, to bound the resident histogram).  Per-slice padding reuses the
    slice's own first element (so the pad lands in an existing bin) and
    its count is subtracted from that bin per eps afterwards.

    The kernel tile resolves via the tuned table (explicit ``tile`` >
    ``tune.qent_tile`` > table cell > ``DEFAULT_TILE``); the histogram
    accumulation is integer, so every tile choice is bit-exact.
    """
    _check_eps(epss)
    k = x.shape[0]
    flat = x.reshape(k, -1).astype(jnp.float32)
    epss = jnp.asarray(epss, jnp.float32).reshape(-1)
    e = epss.shape[0]
    n = flat.shape[1]
    tile = _tune.qent_tile(n, num_bins, tune, tile=tile)
    pad = (-n) % tile
    if pad:
        flat_p = jnp.concatenate(
            [flat, jnp.broadcast_to(flat[:, :1], (k, pad))], axis=1)
    else:
        flat_p = flat
    step = _k.max_eps_per_launch(num_bins)
    hist = jnp.concatenate([                          # (k, e, B)
        _k.qent_histogram_sweep(flat_p, epss[i:i + step], tile=tile,
                                bins=num_bins)
        for i in range(0, e, step)], axis=1)
    if pad:
        first_code = quant.codes(flat[:, :1], epss[None, :])
        idx = first_code % num_bins        # jnp floored-mod: already in [0, B)
        hist = hist.at[jnp.arange(k)[:, None], jnp.arange(e)[None, :], idx
                       ].add(-pad)
    return _ref.entropy_bits_rows(hist)


def quantized_entropy(x: jnp.ndarray, eps, num_bins: int = _k.DEFAULT_BINS,
                      *, tile: Optional[int] = None,
                      tune: Optional[_tune.TuneConfig] = None) -> jnp.ndarray:
    """Entropy (bits/symbol) of one slice at one eps: the (k=1, e=1) case
    of the fused sweep (single implementation of the padding logic)."""
    return quantized_entropy_sweep(
        x.reshape(1, -1), jnp.asarray([eps], jnp.float32), num_bins,
        tile=tile, tune=tune)[0, 0]
