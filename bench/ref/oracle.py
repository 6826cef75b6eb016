"""Plain NumPy reference for the predictor sweep's four columns.

Independent of the program: nothing here imports ``repro``.  The
quantizer's decisions are f32 (``floor(x / eps)`` with IEEE f32
division, saturated to the int32 range), everything else is f64:

* q-ent: Shannon entropy of the codes, from ``np.unique``;
* truncation: fraction of Gram eigenvalues (f64 ``eigvalsh``) that
  reach the variance fraction of a mean-corrected 2-D slice, and the
  cumulative share of the mass at each count, against which a count is
  judged by the share of the mass it misses the variance fraction by
  (a count one off the reference's at a slice whose mass sits within
  float32 rounding of the fraction misses it by that rounding only);
* PSNR and NRMSE of the quantize-dequantize proxy ``codes * eps``.

``lowered=True`` computes the same numbers one precision step below the
configuration's float32: the data and the quotients rounded to
bfloat16, the Gram in float32.  That is the benchmark's control, which
the comparison has to reject.
"""
from __future__ import annotations

import math

import ml_dtypes
import numpy as np

INT32_CODE_MIN = -2147483648.0        # f32-representable int32 range
INT32_CODE_MAX = 2147483520.0
BF16 = ml_dtypes.bfloat16


def codes_f32(flat: np.ndarray, eps, lowered: bool = False) -> np.ndarray:
    """The quantizer's codes: floor(x / eps) in f32, int32-saturated."""
    if lowered:
        q = (flat.astype(BF16).astype(np.float32) / np.float32(eps))
        q = np.floor(q.astype(BF16).astype(np.float32))
    else:
        q = np.floor(flat.astype(np.float32) / np.float32(eps))
    return np.clip(q, INT32_CODE_MIN, INT32_CODE_MAX).astype(np.int64)


def entropy_f64(codes: np.ndarray) -> float:
    _, c = np.unique(codes, return_counts=True)
    p = c / codes.size
    return float(-np.sum(p * np.log2(p)))


def cumulative_f64(x: np.ndarray, lowered: bool = False) -> np.ndarray:
    """Cumulative share of the mass of a mean-corrected 2-D slice's Gram
    eigenvalues, largest first."""
    dt = np.float32 if lowered else np.float64
    u = x.astype(BF16).astype(dt) if lowered else x.astype(dt)
    u = u - u.mean(axis=0, keepdims=True)
    p, q = u.shape
    g = u.T @ u if p >= q else u @ u.T
    ev = np.clip(np.linalg.eigvalsh(g.astype(np.float64)), 0.0, None)[::-1]
    return np.cumsum(ev) / ev.sum()


def truncation_f64(x: np.ndarray, vf: float,
                   lowered: bool = False) -> tuple[float, float]:
    """(fraction of eigenvalues reaching ``vf`` of the mass, the fraction
    one eigenvalue is worth) of a mean-corrected 2-D slice."""
    cum = cumulative_f64(x, lowered)
    return (1 + int(np.sum(cum < vf))) / cum.size, 1.0 / cum.size


def mass_missed(cum: np.ndarray, count: int, vf: float) -> float:
    """Share of the mass by which keeping ``count`` eigenvalues misses the
    variance fraction: 0 for the reference's own count, else how far the
    count's cumulative share lies below ``vf``, or the share one fewer
    reaches above it."""
    reach = 0.0 if count < 1 else float(cum[min(count, cum.size) - 1])
    before = 0.0 if count < 2 else float(cum[min(count - 1, cum.size) - 1])
    return max(0.0, vf - reach, before - vf)


def quality_f64(flat: np.ndarray, eps, lowered: bool = False):
    """(PSNR dB, NRMSE) of the quantize-dequantize proxy, in f64."""
    codes = codes_f32(flat, eps, lowered)
    x = flat.astype(np.float64)
    e = x - codes * np.float64(np.float32(eps))
    mse = float(np.mean(e * e))
    rng = float(x.max() - x.min())
    return 20 * math.log10(rng) - 10 * math.log10(mse), math.sqrt(mse) / rng


def row_reference(x: np.ndarray, ebs, vf: float,
                  lowered: bool = False) -> dict:
    """Everything the comparison needs for one 2-D slice ``x``."""
    flat = np.asarray(x, np.float32).reshape(-1)
    cum = cumulative_f64(np.asarray(x, np.float32), lowered)
    sigma = float(flat.astype(np.float64).std())
    per_eb = [(entropy_f64(codes_f32(flat, e, lowered)),)
              + quality_f64(flat, e, lowered) for e in ebs]
    return {"trunc": (1 + int(np.sum(cum < vf))) / cum.size,
            "step": 1.0 / cum.size, "cum": cum, "vf": vf, "sigma": sigma,
            "per_eb": per_eb}


def deviations(out_row: np.ndarray, ref: dict) -> dict:
    """How far one device row, (e, 4) columns [log q-ent, log(trunc /
    sigma), PSNR, NRMSE] or (e, 2) without the quality pair, lies from
    its reference: q-ent in bits, the truncation by the share of the
    mass its count misses the variance fraction by, PSNR in dB, NRMSE
    relative."""
    quality = out_row.shape[-1] == 4
    d = {"qent_bits": 0.0, "trunc_mass": 0.0}
    if quality:
        d.update(psnr_db=0.0, nrmse_rel=0.0)
    sv = math.exp(float(out_row[0, 1])) * ref["sigma"]
    count = int(round(sv / ref["step"]))
    d["trunc_mass"] = mass_missed(ref["cum"], count, ref["vf"])
    for e, (qent, psnr, nrmse) in enumerate(ref["per_eb"]):
        d["qent_bits"] = max(d["qent_bits"],
                             abs(math.exp(float(out_row[e, 0])) - qent))
        if quality:
            d["psnr_db"] = max(d["psnr_db"],
                               abs(float(out_row[e, 2]) - psnr))
            d["nrmse_rel"] = max(d["nrmse_rel"],
                                 abs(float(out_row[e, 3]) - nrmse) / nrmse)
    return d


def reference_row(ref: dict) -> np.ndarray:
    """A reference's own (e, 4) row in the device's column layout, so
    the control can stand in the program's place."""
    e = len(ref["per_eb"])
    out = np.zeros((e, 4), np.float64)
    for i, (qent, psnr, nrmse) in enumerate(ref["per_eb"]):
        out[i] = (math.log(max(qent, 1e-3)),
                  math.log(max(ref["trunc"], 1e-6) / max(ref["sigma"], 1e-12)),
                  psnr, nrmse)
    return out


def worst(devs) -> dict:
    """Largest deviation of each kind over many rows."""
    out: dict = {}
    for d in devs:
        for k, v in d.items():
            out[k] = max(out.get(k, 0.0), v)
    return out


def compare(rows, vf: float, control: bool = False) -> dict:
    """Worst deviations over ``rows`` of ``(slice, ebs, device row)``
    from the reference.  ``control=True`` puts the reference computed
    one precision step down in the device row's place."""
    def dev(x, ebs, out):
        ref = row_reference(x, ebs, vf)
        if control:
            out = reference_row(row_reference(x, ebs, vf, True))[
                :, :np.shape(out)[-1]]
        return deviations(out, ref)
    return worst(dev(x, ebs, out) for x, ebs, out in rows)
