"""Multivariate phenotype screening (paper abstract: "linear GWAS and
multivariate phenotype screening").

Given the per-batch correlation tile ``R (M, P)`` the engine already
produces, three panel-level screens are provided, all elementwise/reduction
ops over the tile (no extra GEMMs in the scan):

* ``omnibus_chi2``   — ``S_m = N * sum_p r_mp^2``.  If the phenotype panel has
  been *whitened* (decorrelated once, amortized across the scan — the same
  trick the paper uses for residualization), ``S_m ~ chi^2_P`` under the null.
* ``max_abs_t``      — strongest single-trait signal per marker, with a
  Sidak/effective-tests adjusted p-value.
* ``effective_tests``— Li & Ji (2005) eigenvalue-based effective number of
  independent traits, used to calibrate ``max_abs_t``.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import stats as _stats

__all__ = [
    "whiten_panel",
    "omnibus_chi2",
    "max_abs_t",
    "effective_tests",
    "MultivariateScreen",
]


class MultivariateScreen(NamedTuple):
    omnibus: jax.Array        # (M,) chi^2_P statistic
    omnibus_nlp: jax.Array    # (M,) -log10 p
    max_t: jax.Array          # (M,) max_p |t|
    max_t_nlp: jax.Array      # (M,) effective-tests-adjusted -log10 p


def whiten_panel(y_std: jax.Array, *, eig_floor: float = 1e-6) -> tuple[jax.Array, jax.Array]:
    """Whitening matrix for a standardized panel: ``W = V diag(lam^-1/2)``
    so that ``Y W`` has identity trait correlation.

    One ``P x P`` eigendecomposition amortized across the whole genome scan
    (the panel is fixed).  Eigenvalues below ``eig_floor * max`` are dropped
    (their directions carry no independent signal).  Returns ``(W,
    eigenvalues)``; the scan keeps per-trait statistics on the *original*
    panel and applies ``W`` to the correlation tile only (``r @ W``), which
    is algebraically identical to correlating against the whitened panel.
    """
    y = jnp.asarray(y_std, jnp.float32)
    n = y.shape[0]
    corr = jnp.matmul(y.T, y, precision=jax.lax.Precision.HIGHEST) / n
    lam, vec = jnp.linalg.eigh(corr)
    lam = lam[::-1]
    vec = vec[:, ::-1]
    keep = lam > eig_floor * lam[0]
    scale = jnp.where(keep, jax.lax.rsqrt(jnp.maximum(lam, eig_floor)), 0.0)
    w = vec * scale[None, :]
    return w, lam


def omnibus_chi2(
    r_tile: jax.Array,
    n_samples: int,
    n_traits_eff: float,
    whitening: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Panel omnibus: ``S = N * sum_p r_w^2 ~ chi^2_{P_eff}`` where
    ``r_w = r @ W`` decorrelates the traits (pass ``whitening=None`` only if
    the panel was already whitened)."""
    if whitening is not None:
        r_tile = jnp.matmul(r_tile, whitening, precision=jax.lax.Precision.HIGHEST)
    s = jnp.asarray(n_samples, jnp.float32) * jnp.sum(jnp.square(r_tile), axis=-1)
    nlp = _stats.neglog10_sf_chi2(s, n_traits_eff)
    return s, nlp


def max_abs_t(
    t_tile: jax.Array, dof: int, n_traits_eff: float
) -> tuple[jax.Array, jax.Array]:
    """Strongest per-marker hit with Sidak correction by the effective test
    count: ``p_adj = 1 - (1 - p_min)^Meff``; in -log10 space use the stable
    ``p_adj ~ Meff * p_min`` for small p (the only regime anyone screens)."""
    tmax = jnp.max(jnp.abs(t_tile), axis=-1)
    nlp = _stats.neglog10_p_from_t(tmax, dof)
    nlp_adj = jnp.maximum(nlp - jnp.log10(jnp.asarray(n_traits_eff, jnp.float32)), 0.0)
    return tmax, nlp_adj


def effective_tests(eigenvalues: jax.Array) -> jax.Array:
    """Li & Ji (2005): ``Meff = sum_i I(lam_i >= 1) + (lam_i - floor(lam_i))``
    over eigenvalues of the trait correlation matrix."""
    lam = jnp.maximum(jnp.asarray(eigenvalues, jnp.float32), 0.0)
    return jnp.sum(jnp.where(lam >= 1.0, 1.0, 0.0) + (lam - jnp.floor(lam)))


def screen(
    r_tile: jax.Array,
    t_tile: jax.Array,
    *,
    n_samples: int,
    dof: int,
    n_traits_eff: float,
) -> MultivariateScreen:
    omni, omni_nlp = omnibus_chi2(r_tile, n_samples, n_traits_eff)
    tmax, tmax_nlp = max_abs_t(t_tile, dof, n_traits_eff)
    return MultivariateScreen(omnibus=omni, omnibus_nlp=omni_nlp, max_t=tmax, max_t_nlp=tmax_nlp)
