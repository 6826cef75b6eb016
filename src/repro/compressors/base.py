"""Compressor API + registry.

Every compressor exposes:
  * ``encode(data, eps)   -> (codes, aux)``   decorrelate+quantize: one
                                              compiled program per input
                                              shape and dtype
  * ``decode(codes, aux, eps) -> recon``      reconstruction, compiled
                                              the same way
  * ``size_bytes(codes, aux, eps) -> int``    host-side real byte count
                                              (zstd-backed entropy stage)
  * ``cr(data, eps) -> float``                original_bytes / compressed

The decorrelation/quantization stages run in JAX (TPU-lowera­ble, some with
Pallas kernels); the final entropy-coding stage is host-side (zstandard),
exactly mirroring real compressor pipelines (SZ: Huffman+zstd, MGARD: zlib/
zstd, Bit Grooming: generic lossless coder).  CR labels used to train the
paper's regressions are therefore *real measured ratios*.
"""
from __future__ import annotations

import abc
import functools
from typing import Any, Dict, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro import obs


def compiled(compressor: str, static: Tuple[str, ...] = ()):
    """``jax.jit`` for a compressor stage ``fn(*arrays, **static)``.

    The error bound is one of the traced arrays, never static: a Python
    or NumPy float is passed as a float32 scalar, so every ``eps`` of a
    shape shares one program.  Static arguments (shapes, level counts)
    are keyword-only.  The Python body records a
    ``repro.compress.trace`` span (``compressor``; ``shape``, that of the
    first input's first array), so the span marks each trace: once per
    (stage, input shapes and dtypes) in a process.
    """
    def wrap(fn):
        @functools.wraps(fn)
        def traced(*args, **kw):
            shape = tuple(jnp.shape(jax.tree.leaves(args[0])[0]))
            with obs.span("repro.compress.trace", compressor=compressor,
                          shape=shape):
                return fn(*jax.tree.map(jnp.asarray, args), **kw)

        program = jax.jit(traced, static_argnames=static)

        @functools.wraps(fn)
        def call(*args, **kw):
            return program(*(np.float32(a) if isinstance(a, (float, np.floating))
                             else a for a in args), **kw)
        return call
    return wrap


class Compressor(abc.ABC):
    name: str = "base"
    supports_3d: bool = True

    @abc.abstractmethod
    def encode(self, data: jnp.ndarray, eps: float) -> Tuple[Any, Dict[str, Any]]:
        ...

    @abc.abstractmethod
    def decode(self, codes: Any, aux: Dict[str, Any], eps: float) -> jnp.ndarray:
        ...

    @abc.abstractmethod
    def size_bytes(self, codes: Any, aux: Dict[str, Any], eps: float) -> int:
        ...

    # ------------------------------------------------------------------
    def cr(self, data: jnp.ndarray, eps: float) -> float:
        """Measured compression ratio (original fp32 bytes / compressed)."""
        with obs.span("repro.compress.run", compressor=self.name,
                      eps=eps) as run:
            with obs.span("repro.compress.encode"):
                codes, aux = self.encode(data, eps)
            with obs.span("repro.compress.size"):
                size = self.size_bytes(codes, aux, eps)
            run.attrs["bytes"] = size
        return float(data.size * 4) / max(size, 1)

    def roundtrip_error(self, data: jnp.ndarray, eps: float) -> float:
        codes, aux = self.encode(data, eps)
        recon = self.decode(codes, aux, eps)
        return float(jnp.max(jnp.abs(recon - data)))


def error_bound_slack(data: jnp.ndarray) -> float:
    """fp32 representability floor for quantizer-grid reconstructions.

    Reconstruction values fl(q * 2eps) are spaced 2eps +- 1 ulp(|d|) apart, so
    the best achievable max error is eps + ulp/2: for |d| >> eps no integer
    code can do better.  Real SZ escapes this by storing such points verbatim
    ('unpredictable values'); our branch-free parallel quantizer accepts the
    floor instead (documented in DESIGN.md).  Tests assert
    err <= eps + error_bound_slack(data).
    """
    return float(jnp.max(jnp.abs(data))) * 2.0 ** -23


_REGISTRY: Dict[str, Compressor] = {}


def register(comp: Compressor) -> Compressor:
    _REGISTRY[comp.name] = comp
    return comp


def get(name: str) -> Compressor:
    return _REGISTRY[name]


def names() -> list[str]:
    return sorted(_REGISTRY)


def all_compressors() -> Dict[str, Compressor]:
    return dict(_REGISTRY)
