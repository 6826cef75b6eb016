"""Pure-jnp oracle for the qent kernel."""
import jax
import jax.numpy as jnp

from repro import f32, quant


def qent_histogram(x: jnp.ndarray, eps, bins: int = 4096) -> jnp.ndarray:
    codes = quant.codes(x.reshape(-1), eps)
    idx = jax.lax.rem(codes, bins)
    idx = jnp.where(idx < 0, idx + bins, idx)
    return jnp.zeros((bins,), jnp.int32).at[idx].add(1)


def entropy_bits(hist: jnp.ndarray) -> jnp.ndarray:
    n = jnp.maximum(jnp.sum(hist), 1)
    p = hist / n
    return -jnp.sum(jnp.where(p > 0, p * f32.log2(jnp.maximum(p, 1e-30)), 0.0))


def quantized_entropy(x: jnp.ndarray, eps, bins: int = 4096) -> jnp.ndarray:
    return entropy_bits(qent_histogram(x, eps, bins))


def entropy_bits_rows(hist: jnp.ndarray) -> jnp.ndarray:
    """Entropy along the last (bins) axis of a histogram stack."""
    n = jnp.maximum(jnp.sum(hist, axis=-1, keepdims=True), 1)
    p = hist / n
    return -jnp.sum(jnp.where(p > 0, p * f32.log2(jnp.maximum(p, 1e-30)), 0.0),
                    axis=-1)


def qent_histogram_sweep(x: jnp.ndarray, epss, bins: int = 4096) -> jnp.ndarray:
    """Oracle for the sweep kernel: (k, ...) x (e,) -> (k, e, bins)."""
    k = x.shape[0]
    flat = x.reshape(k, -1)
    return jnp.stack([
        jnp.stack([qent_histogram(flat[s], eps, bins) for eps in epss])
        for s in range(k)])


def quantized_entropy_sweep(x: jnp.ndarray, epss, bins: int = 4096) -> jnp.ndarray:
    return entropy_bits_rows(qent_histogram_sweep(x, epss, bins))
