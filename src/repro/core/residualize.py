"""Covariate handling: orthonormal basis construction and panel residualization.

Implements paper Eq. (1):  ``Y_res = (I - Q Q^T)(Y - Ybar)`` with ``Q`` an
orthonormal basis spanning the covariate space, followed by column-wise
standardization to unit (population) variance.

Design choices (documented in DESIGN.md §8):

* ``Q`` always includes the intercept column, so mean-centering and
  residualization are a single projection.  ``Q`` comes from a reduced QR of
  the ``[1 | C]`` matrix with rank detection (collinear covariates are
  dropped, matching what LAPACK-based tools do silently).
* Standardization uses the population variance (``ddof=0``) so that the
  downstream ``R = G Y / N`` is *exactly* the Pearson correlation of the
  residualized data.
* ``exact`` mode residualizes the genotype batch with the same ``Q``
  (Frisch-Waugh-Lovell), making the t statistic identical to the full
  per-trait OLS with covariates.  The paper's release residualizes Y only;
  both modes ship, the paper's is the default.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

__all__ = [
    "covariate_basis",
    "residualize_and_standardize",
    "residualize_genotypes",
    "StandardizedPanel",
]


class StandardizedPanel(NamedTuple):
    """Residualized + standardized phenotype panel ready for the scan."""

    y: jax.Array          # (N, P) float32, zero mean, unit population variance
    valid: jax.Array      # (P,) bool — False where the residual variance was ~0
    n_samples: int
    n_covariates: int     # columns of Q *excluding* the intercept


def covariate_basis(
    covariates: jax.Array | None,
    n_samples: int,
    *,
    rank_tol: float = 1e-5,
) -> jax.Array:
    """Orthonormal basis ``Q (N, q+1)`` of ``span([1 | C])``.

    Covariates are centered and scaled to unit variance first (the span is
    unchanged once the intercept is present, and the QR diagonal becomes a
    meaningful relative rank signal in float32).  Rank-deficient (collinear)
    columns are zeroed out of the basis: zero columns in Q are harmless in
    the projection ``Q Q^T``.  ``rank_tol=1e-5`` matches f32 QR roundoff for
    exactly-collinear inputs.
    """
    ones = jnp.ones((n_samples, 1), jnp.float32)
    if covariates is None:
        mat = ones
    else:
        cov = jnp.asarray(covariates, jnp.float32)
        if cov.ndim == 1:
            cov = cov[:, None]
        cov = cov - jnp.mean(cov, axis=0, keepdims=True)
        std = jnp.std(cov, axis=0, keepdims=True)
        cov = cov / jnp.maximum(std, 1e-12)
        mat = jnp.concatenate([ones, cov], axis=1)
    q, r = jnp.linalg.qr(mat, mode="reduced")
    diag = jnp.abs(jnp.diagonal(r))
    keep = diag > rank_tol * jnp.max(diag)
    return q * keep[None, :].astype(q.dtype)


def _dot(a: jax.Array, b: jax.Array) -> jax.Array:
    # f32 products: the TPU's default contracts f32 in one bf16 pass, which
    # leaves bf16-sized error in every residual (PERF.md).
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _project_out(x: jax.Array, q: jax.Array) -> jax.Array:
    """``(I - Q Q^T) x`` without materializing the N x N projector."""
    return x - _dot(q, _dot(q.T, x))


def residualize_and_standardize(
    y: jax.Array,
    q: jax.Array,
    *,
    var_tol: float = 1e-10,
) -> StandardizedPanel:
    """Paper Eq. (1) + column standardization.

    Returns the standardized panel and a validity mask for phenotypes whose
    residual variance collapsed (constant columns, or columns exactly in the
    covariate span).  Invalid columns are zeroed so they contribute r = 0.
    """
    y = jnp.asarray(y, jnp.float32)
    n = y.shape[0]
    y_res = _project_out(y, q)
    # Population variance of the residuals (they are mean-zero by construction
    # because Q contains the intercept).
    var = jnp.mean(jnp.square(y_res), axis=0)
    valid = var > var_tol
    inv_std = jnp.where(valid, jax.lax.rsqrt(jnp.maximum(var, var_tol)), 0.0)
    y_std = y_res * inv_std[None, :]
    return StandardizedPanel(
        y=y_std,
        valid=valid,
        n_samples=n,
        n_covariates=int(q.shape[1]) - 1,
    )


def residualize_genotypes(g_std: jax.Array, q: jax.Array, *, var_tol: float = 1e-10) -> jax.Array:
    """FWL 'exact' mode: project covariates out of a standardized genotype
    batch ``(M, N)`` and re-standardize rows.

    After this, ``R = G Y / N`` with the exact dof ``N - 2 - q`` reproduces
    full covariate-adjusted OLS t statistics (validated in
    ``tests/test_residualize.py`` against a direct lstsq fit).
    """
    g = jnp.asarray(g_std, jnp.float32)
    g_res = g - _dot(_dot(g, q), q.T)
    var = jnp.mean(jnp.square(g_res), axis=1)
    valid = var > var_tol
    inv_std = jnp.where(valid, jax.lax.rsqrt(jnp.maximum(var, var_tol)), 0.0)
    return g_res * inv_std[:, None]
