"""Regression models for CR prediction (paper section 3.2), in pure JAX.

* ``LinearCRModel``  -- Eq. (1): log(CR) = a + b*log(qent) + c*log(svd/sigma)
                        + d * interaction, least squares.
* ``SplineCRModel``  -- Eq. (2): GAM with natural cubic splines (3 knots) per
                        predictor + tensor-product interaction, penalized LS.
* ``lasso_path``     -- LASSO (FISTA) for predictor-importance analysis
                        (Table 3 / Fig 8 analogues).

All models operate on standardized predictors and log(CR) targets, mirroring
the paper ("statistical predictors are standardized ... we consider the
logarithm of CRs").  R's lm/mgcv/glmnet are replaced by closed-form /
iterative JAX solvers (validated against scipy in tests).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp


def _mm(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """f32 matrix product.  At default precision the TPU rounds f32
    operands to bf16, which turns the cubic spline designs' normal
    equations into noise."""
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


class Standardizer(NamedTuple):
    mean: jnp.ndarray
    std: jnp.ndarray

    @staticmethod
    def fit(x: jnp.ndarray) -> "Standardizer":
        return Standardizer(jnp.mean(x, axis=0), jnp.maximum(jnp.std(x, axis=0), 1e-8))

    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        return (x - self.mean) / self.std


# ---------------------------------------------------------------------------
# Linear model (Eq. 1)
# ---------------------------------------------------------------------------

def _linear_design(z: jnp.ndarray) -> jnp.ndarray:
    """[1, z1, z2, z1*z2] design from standardized predictors (n, 2)."""
    one = jnp.ones((z.shape[0], 1), z.dtype)
    inter = (z[:, 0] * z[:, 1])[:, None]
    return jnp.concatenate([one, z, inter], axis=1)


class LinearCRModel(NamedTuple):
    """log(CR) ~ a + b z1 + c z2 + d z1 z2 with standardized predictors."""
    std: Standardizer
    coef: jnp.ndarray          # (4,)

    @staticmethod
    def fit(features: jnp.ndarray, cr: jnp.ndarray, ridge: float = 1e-8) -> "LinearCRModel":
        std = Standardizer.fit(features)
        x = _linear_design(std(features))
        y = jnp.log(cr)
        xtx = _mm(x.T, x) + ridge * jnp.eye(x.shape[1])
        coef = jnp.linalg.solve(xtx, _mm(x.T, y))
        return LinearCRModel(std, coef)

    def predict(self, features: jnp.ndarray) -> jnp.ndarray:
        x = _linear_design(self.std(features))
        return jnp.exp(_mm(x, self.coef))

    def predict_log(self, features: jnp.ndarray) -> jnp.ndarray:
        return _mm(_linear_design(self.std(features)), self.coef)


# ---------------------------------------------------------------------------
# Natural cubic spline basis (ESL section 5.2.1), K knots -> K basis funcs
# ---------------------------------------------------------------------------

def ncs_basis(x: jnp.ndarray, knots: jnp.ndarray) -> jnp.ndarray:
    """Natural cubic spline basis N(x): (n,) -> (n, K).

    N1 = 1, N2 = x, N_{k+2} = d_k - d_{K-1} with
    d_k(x) = ((x - xi_k)^3_+ - (x - xi_K)^3_+) / (xi_K - xi_k).
    """
    k = knots.shape[0]

    def d(j):
        num = jnp.maximum(x - knots[j], 0.0) ** 3 - jnp.maximum(x - knots[k - 1], 0.0) ** 3
        return num / (knots[k - 1] - knots[j])

    cols = [jnp.ones_like(x), x]
    d_last = d(k - 2)
    for j in range(k - 2):
        cols.append(d(j) - d_last)
    return jnp.stack(cols, axis=1)


def _quantile_knots(z: jnp.ndarray, num_knots: int) -> jnp.ndarray:
    qs = jnp.linspace(0.05, 0.95, num_knots)
    knots = jnp.quantile(z, qs)
    # Degenerate guard: strictly increasing knots.
    return knots + jnp.arange(num_knots) * 1e-6


def _spline_design(z: jnp.ndarray, knots1: jnp.ndarray, knots2: jnp.ndarray) -> jnp.ndarray:
    """GAM design: s(z1) + s(z2) + ti(z1, z2).

    Columns: [1, N1_nonconst(z1), N2_nonconst(z2), outer(ti-parts)].
    """
    b1 = ncs_basis(z[:, 0], knots1)          # (n, K)
    b2 = ncs_basis(z[:, 1], knots2)          # (n, K)
    smooth1 = b1[:, 1:]                       # drop shared intercept
    smooth2 = b2[:, 1:]
    # tensor-product interaction of the non-constant parts
    ti = (smooth1[:, :, None] * smooth2[:, None, :]).reshape(z.shape[0], -1)
    one = jnp.ones((z.shape[0], 1), z.dtype)
    return jnp.concatenate([one, smooth1, smooth2, ti], axis=1)


class SplineCRModel(NamedTuple):
    """GAM (Eq. 2): cubic splines + tensor-product interaction, 3 knots."""
    std: Standardizer
    knots1: jnp.ndarray
    knots2: jnp.ndarray
    coef: jnp.ndarray

    @staticmethod
    def fit(
        features: jnp.ndarray,
        cr: jnp.ndarray,
        num_knots: int = 3,
        ridge: float = 1e-4,
    ) -> "SplineCRModel":
        std = Standardizer.fit(features)
        z = std(features)
        knots1 = _quantile_knots(z[:, 0], num_knots)
        knots2 = _quantile_knots(z[:, 1], num_knots)
        x = _spline_design(z, knots1, knots2)
        y = jnp.log(cr)
        # Penalized LS; don't penalize intercept.
        pen = ridge * jnp.eye(x.shape[1]).at[0, 0].set(0.0)
        coef = jnp.linalg.solve(_mm(x.T, x) + pen, _mm(x.T, y))
        return SplineCRModel(std, knots1, knots2, coef)

    def predict(self, features: jnp.ndarray) -> jnp.ndarray:
        x = _spline_design(self.std(features), self.knots1, self.knots2)
        return jnp.exp(_mm(x, self.coef))

    def predict_log(self, features: jnp.ndarray) -> jnp.ndarray:
        x = _spline_design(self.std(features), self.knots1, self.knots2)
        return _mm(x, self.coef)


# ---------------------------------------------------------------------------
# LASSO via FISTA (predictor importance, Table 3)
# ---------------------------------------------------------------------------

def _soft_threshold(x: jnp.ndarray, t: float) -> jnp.ndarray:
    return jnp.sign(x) * jnp.maximum(jnp.abs(x) - t, 0.0)


@partial(jax.jit, static_argnames=("num_iters",))
def lasso_fit(x: jnp.ndarray, y: jnp.ndarray, lam: jnp.ndarray, num_iters: int = 500) -> jnp.ndarray:
    """min_b 1/(2n) ||y - X b||^2 + lam ||b_{1:}||_1 (intercept unpenalized).

    FISTA with fixed step 1/L, L = largest eigenvalue of X^T X / n.
    Returns coefficient vector (p,).
    """
    n = x.shape[0]
    xtx = _mm(x.T, x) / n
    xty = _mm(x.T, y) / n
    lipschitz = jnp.linalg.eigvalsh(xtx)[-1] + 1e-8
    step = 1.0 / lipschitz
    mask = jnp.ones(x.shape[1]).at[0].set(0.0)  # don't penalize intercept

    def body(_, carry):
        b, v, t = carry
        grad = _mm(xtx, v) - xty
        b_new = _soft_threshold(v - step * grad, step * lam * 1.0) * mask + \
            (v - step * grad) * (1 - mask)
        t_new = (1 + jnp.sqrt(1 + 4 * t * t)) / 2
        v_new = b_new + ((t - 1) / t_new) * (b_new - b)
        return b_new, v_new, t_new

    b0 = jnp.zeros(x.shape[1])
    b, _, _ = jax.lax.fori_loop(0, num_iters, body, (b0, b0, jnp.array(1.0)))
    return b


def lasso_importance(
    features: jnp.ndarray,
    cr: jnp.ndarray,
    lam_grid: jnp.ndarray | None = None,
    k: int = 8,
    seed: int = 0,
) -> jnp.ndarray:
    """Cross-validated LASSO on the Eq.-(1) design; returns |coef| for
    [qent, svd/sigma, interaction] -- the paper's Table 3 numbers.
    """
    std = Standardizer.fit(features)
    x = _linear_design(std(features))
    y = jnp.log(cr)
    y_mean, y_std = jnp.mean(y), jnp.maximum(jnp.std(y), 1e-8)
    yz = (y - y_mean) / y_std
    if lam_grid is None:
        lam_grid = jnp.logspace(-4, 0, 20)

    n = x.shape[0]
    perm = jax.random.permutation(jax.random.PRNGKey(seed), n)
    folds = jnp.array_split(perm, k)

    def cv_err(lam):
        errs = []
        for f in folds:
            test_mask = jnp.zeros(n, bool).at[f].set(True)
            w = (~test_mask).astype(x.dtype)
            # weighted LS via FISTA on weighted matrices
            xw = x * w[:, None]
            b = lasso_fit(xw, yz * w, lam)
            resid = (_mm(x, b) - yz) * test_mask
            errs.append(jnp.sum(resid**2) / jnp.maximum(jnp.sum(test_mask), 1))
        return jnp.mean(jnp.stack(errs))

    errs = jnp.stack([cv_err(l) for l in lam_grid])
    best = lam_grid[jnp.argmin(errs)]
    coef = lasso_fit(x, yz, best)
    return jnp.abs(coef[1:])  # drop intercept: [qent, svd/sigma, interaction]


# jitted whole-model evaluation: models are NamedTuple pytrees, so one
# compile serves every instance with the same knot count
@jax.jit
def predict_fast(model, feats: jnp.ndarray) -> jnp.ndarray:
    return model.predict(feats)


MODEL_REGISTRY: dict[str, Callable] = {
    "linear": LinearCRModel.fit,
    "spline": SplineCRModel.fit,
}
