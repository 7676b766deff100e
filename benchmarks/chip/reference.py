"""The plain reference: the paper's OLS scan (Eq. 1-3) in float64 on the host.

It reads only the cell's data (packed pool, phenotypes, covariates) and
imports nothing of the program.  Covariates are centred, scaled and
projected out of each phenotype with an intercept (Eq. 1); phenotypes and
genotypes are standardized to unit population variance, a missing call
taking the marker's mean; ``r = g . y / N``, ``t = r sqrt(dof / (1 - r^2))``
with ``dof = N - 2`` (the paper's Eq. 3), and -log10 p is the two-sided
Student-t tail.

``LowerPrecision`` is the control: the same arithmetic with the dot in
three bf16 passes (TPU ``Precision.HIGH``, the step below the configured
``HIGHEST``) and t and -log10 p rounded to bfloat16 (the step below plain
float32), put in the program's place.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np
from scipy import stats as sps

from genome import decode

ROW_CHUNK = 512
THREADS = min(16, os.cpu_count() or 1)


def _chunks(fn, n: int) -> list:
    """``fn(lo, hi)`` over row chunks, on a few threads (numpy lets go of the GIL)."""
    spans = [(lo, min(lo + ROW_CHUNK, n)) for lo in range(0, n, ROW_CHUNK)]
    with ThreadPoolExecutor(THREADS) as pool:
        return list(pool.map(lambda s: fn(*s), spans))


class Reference:
    def __init__(self, pool: np.ndarray, phenotypes: np.ndarray,
                 covariates: np.ndarray | None, n_samples: int):
        self.pool = pool
        self.y_raw = phenotypes
        self.n = int(n_samples)
        self.dof = float(self.n - 2)
        basis = [np.ones((self.n, 1))]
        if covariates is not None and covariates.size:
            c = np.asarray(covariates, np.float64)
            basis.append((c - c.mean(0)) / c.std(0))
        self.q, _ = np.linalg.qr(np.concatenate(basis, axis=1))

    def panel(self, traits: np.ndarray) -> np.ndarray:
        """``(N, k)`` residualized, standardized phenotypes of ``traits``."""
        y = np.asarray(self.y_raw[:, traits], np.float64)
        y -= self.q @ (self.q.T @ y)
        scale = np.sqrt(np.mean(y * y, axis=0))
        return y / np.where(scale > 0, scale, np.inf)

    def genotypes(self, rows: np.ndarray) -> np.ndarray:
        """``(k, N)`` standardized dosages of pool ``rows``, a missing call
        at the marker's mean; a monomorphic marker is a row of zeros."""
        d = decode(self.pool[rows], self.n)
        present = d >= 0
        d = np.where(present, d, np.int8(0))
        count = np.maximum(present.sum(1), 1)
        mean = d.sum(1, dtype=np.int64) / count
        # population variance of the imputed row: missing calls add nothing
        var = ((d.astype(np.int64) ** 2).sum(1) / count - mean**2) * count / self.n
        g = d.astype(np.float64)
        g -= present * mean[:, None]
        return g * np.where(var > 1e-10, 1.0 / np.sqrt(np.maximum(var, 1e-10)), 0.0)[:, None]

    def r_block(self, rows: np.ndarray, y: np.ndarray) -> np.ndarray:
        """``(len(rows), k)`` correlations of pool ``rows`` with panel ``y``."""
        if not len(rows):
            return np.zeros((0, y.shape[1]))
        return np.concatenate(_chunks(lambda a, b: self.genotypes(rows[a:b]) @ y / self.n,
                                      len(rows)))

    def r_pairs(self, rows: np.ndarray, traits: np.ndarray) -> np.ndarray:
        """``r`` of each (pool row, trait) pair."""
        order = np.argsort(rows, kind="stable")

        def some(a, b):
            sel = order[a:b]
            return np.einsum("kn,nk->k", self.genotypes(rows[sel]), self.panel(traits[sel]))

        out = np.empty(len(rows))
        if len(rows):
            out[order] = np.concatenate(_chunks(some, len(rows))) / self.n
        return out

    def t(self, r: np.ndarray) -> np.ndarray:
        return r * np.sqrt(self.dof / np.maximum(1.0 - r * r, 1e-300))

    def nlp(self, t: np.ndarray) -> np.ndarray:
        return -(sps.t.logsf(np.abs(t), self.dof) + np.log(2.0)) / np.log(10.0)


def _bf16(x: np.ndarray) -> np.ndarray:
    import jax.numpy as jnp

    return np.asarray(jnp.asarray(x, jnp.float32).astype(jnp.bfloat16).astype(jnp.float32))


class LowerPrecision(Reference):
    """The reference one precision step down, in the program's place."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        import jax
        import jax.numpy as jnp

        def split(x):
            hi = x.astype(jnp.bfloat16)
            return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)

        def dot3(g, y):
            # TPU Precision.HIGH: a_hi b_hi + a_hi b_lo + a_lo b_hi, each pass
            # bf16 x bf16 with float32 accumulation.
            (g_hi, g_lo), (y_hi, y_lo) = split(g), split(y)
            f32 = jnp.float32
            small = (jnp.matmul(g_hi, y_lo, preferred_element_type=f32)
                     + jnp.matmul(g_lo, y_hi, preferred_element_type=f32))
            return small + jnp.matmul(g_hi, y_hi, preferred_element_type=f32)

        self._dot3 = jax.jit(dot3)

    def r_block(self, rows, y):
        import jax.numpy as jnp

        y32 = jnp.asarray(y, jnp.float32)
        return np.concatenate([
            np.asarray(self._dot3(jnp.asarray(self.genotypes(rows[i:i + ROW_CHUNK]),
                                              jnp.float32), y32), np.float64) / self.n
            for i in range(0, len(rows), ROW_CHUNK)
        ])

    def t(self, r):
        return _bf16(super().t(np.asarray(r, np.float32).astype(np.float64)))

    def nlp(self, t):
        return _bf16(super().nlp(t))
