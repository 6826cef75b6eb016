"""Plain reference for the advisor's training compressor runs.

Independent of the program: nothing here imports ``repro``.  Given the
codes a compressor produced for one 2-D slice, it counts their
compressed bytes with the study compressors' entropy stage, as the
compressors document it: integer codes packed in the narrowest of
int8 and int16 (values past int16 clipped and stored out of band at 8
bytes each), zstd at level 3, a 32-byte header per coded array; raw
zstd over the f32 values for Bit Grooming and Digit Rounding; ZFP's
embedded bit-plane size, whose bit lengths ZFP defines as
``ceil(log2(|q| + 1))`` in float32.  From the bytes comes the
compression ratio (original f32 bytes over compressed bytes) that the
advisor's models train on.

``bound_ratio`` checks the compressors' absolute error bound: the
largest ``|x - reconstruction|`` over ``eps`` plus the f32 floor of the
grid the values are reconstructed on (one ulp of the largest ``|x|``).
"""
from __future__ import annotations

import math

import numpy as np
import zstandard

HEADER = 32
_CCTX = zstandard.ZstdCompressor(level=3)
I8, I16 = np.iinfo(np.int8), np.iinfo(np.int16)


def zstd_len(payload: bytes) -> int:
    return len(_CCTX.compress(payload))


def coded(codes) -> int:
    """Bytes of one array of integer codes."""
    c = np.asarray(codes).astype(np.int64)
    lo, hi = int(c.min()), int(c.max())
    out = 0
    if I8.min <= lo and hi <= I8.max:
        payload = c.astype(np.int8).tobytes()
    elif I16.min <= lo and hi <= I16.max:
        payload = c.astype(np.int16).tobytes()
    else:
        clipped = np.clip(c, I16.min + 1, I16.max)
        out = 8 * int(np.sum(clipped != c))
        payload = clipped.astype(np.int16).tobytes()
    return zstd_len(payload) + out + HEADER


def raw(values) -> int:
    """Bytes of f32 values through zstd alone."""
    return zstd_len(np.asarray(values, np.float32).tobytes()) + HEADER


def _interp(codes) -> int:
    if str(codes[0]) == "root":
        return coded(codes[1])
    _, sub, codes_c, codes_r, _ = codes
    return _interp(sub) + coded(codes_c) + coded(codes_r)


def _zfp(codes, aux, eps: float) -> int:
    q = np.abs(np.asarray(codes).astype(np.int64))
    e = np.asarray(aux["e"]).astype(np.int64)
    ndim = q.ndim - 1
    lsb = e - 24                                  # 2^(e - (26 - 2))
    cut = math.floor(math.log2(float(np.float32(eps)))) - lsb - (1 + ndim)
    cut = np.maximum(cut, 0).reshape((-1,) + (1,) * ndim)
    mag = q.astype(np.float32)
    bitlen = np.where(q > 0, np.ceil(np.log2(mag + np.float32(1.0))), 0.0)
    kept = np.maximum(bitlen - cut, 0.0)
    bits = np.sum(kept + (kept > 0)) + q.shape[0] * (8 + 2 * 4 ** ndim / 4)
    return int(math.ceil(bits / 8.0))


def size_bytes(name: str, codes, aux, eps: float) -> int:
    """Compressed bytes of one run of compressor ``name``."""
    if name == "sz3-lorenzo":
        return coded(codes)
    if name == "sz3-regression":
        return coded(codes) + coded(aux["coef_codes"])
    if name == "sz3-interp":
        return _interp(codes)
    if name == "sz2":
        use = np.asarray(aux["use_reg"]).astype(bool)
        total = coded(codes) + int(math.ceil(use.size / 8))
        cq = np.asarray(aux["coef_codes"])[use]
        return total + (coded(cq) if cq.size else 0)
    if name == "mgard":
        root, levels = codes
        return coded(root) + sum(coded(c) for c in levels)
    if name == "zfp":
        return _zfp(codes, aux, eps)
    if name in ("bitgrooming", "digitrounding"):
        return raw(codes)
    raise KeyError(f"no reference size for compressor {name!r}")


def ratio(x: np.ndarray, nbytes: int) -> float:
    """Compression ratio: the slice's f32 bytes over ``nbytes``."""
    return 4.0 * np.asarray(x).size / max(int(nbytes), 1)


def bound_ratio(x: np.ndarray, recon: np.ndarray, eps: float) -> float:
    """Largest error over the bound: at most 1 where the bound holds."""
    x64 = np.asarray(x, np.float64)
    err = float(np.max(np.abs(x64 - np.asarray(recon, np.float64))))
    floor = float(np.max(np.abs(x64))) * 2.0 ** -23
    return err / (float(eps) + floor)
