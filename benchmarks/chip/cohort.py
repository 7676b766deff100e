"""The cell's data, made from ``--seed`` on the device in one jitted call.

A pool of distinct markers (PLINK 2-bit bytes, MAF uniform on the
configured range, missing calls at the configured rate, as
``repro.io.synth.make_cohort`` draws them), a covariate matrix, and a
phenotype panel: unit-variance noise, covariate loadings, and planted
effects.  Every seed plants the same number of (marker, trait) pairs,
spread evenly over the pool's batch-sized segments, so every cell of every
seed carries the same number of hits; the seed changes which ones.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np


@dataclass
class Cohort:
    pool: np.ndarray            # (distinct_markers, ceil(N/4)) uint8, PLINK bytes
    phenotypes: np.ndarray      # (N, P) float32
    covariates: np.ndarray      # (N, C) float32


def seed_sequence(seed: int) -> np.random.SeedSequence:
    """Any whole number, negative or past 64 bits, to a seed sequence."""
    return np.random.SeedSequence([abs(int(seed)), int(seed < 0)])


def plant(rng: np.random.Generator, *, n_traits: int, distinct: int, segment: int,
          density: float) -> tuple[np.ndarray, np.ndarray]:
    """``round(density * distinct * n_traits)`` planted pairs, at most one per
    trait, dealt round-robin over the pool's ``segment``-sized stretches."""
    k = min(n_traits, int(round(density * distinct * n_traits)))
    traits = rng.permutation(n_traits)[:k]
    n_seg = distinct // segment
    rows = (np.arange(k) % n_seg) * segment + rng.integers(0, segment, size=k)
    return rows.astype(np.int32), traits.astype(np.int32)


@functools.partial(
    jax.jit,
    static_argnames=("n", "p", "c", "distinct", "segment", "maf_range", "missing_rate",
                     "loading_sd"),
)
def _generate(key, rows, traits, beta, *, n, p, c, distinct, segment, maf_range,
              missing_rate, loading_sd):
    k_maf, k_geno, k_miss, k_noise, k_cov, k_load = jax.random.split(key, 6)
    maf = jax.random.uniform(k_maf, (distinct,), minval=maf_range[0], maxval=maf_range[1])
    n_bytes = -(-n // 4)

    def one_segment(i):
        f = jax.lax.dynamic_slice(maf, (i * segment,), (segment,))[:, None]
        u = jax.random.uniform(jax.random.fold_in(k_geno, i), (2, segment, n))
        dosage = (u[0] < f).astype(jnp.int8) + (u[1] < f).astype(jnp.int8)
        missing = jax.random.uniform(jax.random.fold_in(k_miss, i), (segment, n)) < missing_rate
        code = jnp.where(missing, 1, jnp.where(dosage == 2, 0, jnp.where(dosage == 1, 2, 3)))
        code = jnp.pad(code.astype(jnp.uint8), ((0, 0), (0, 4 * n_bytes - n)),
                       constant_values=3).reshape(segment, n_bytes, 4)
        packed = code[..., 0] | (code[..., 1] << 2) | (code[..., 2] << 4) | (code[..., 3] << 6)
        return packed, dosage

    packed, dosage = jax.lax.map(one_segment, jnp.arange(distinct // segment))
    packed = packed.reshape(distinct, n_bytes)
    g = dosage.reshape(distinct, n)[rows].astype(jnp.float32)
    g = g - jnp.mean(g, axis=1, keepdims=True)
    g = g * jax.lax.rsqrt(jnp.maximum(jnp.mean(g * g, axis=1, keepdims=True), 1e-12))
    cov = jax.random.normal(k_cov, (n, c))
    loading = loading_sd * jax.random.normal(k_load, (c, p))
    y = jax.random.normal(k_noise, (n, p)) + jnp.matmul(
        cov, loading, precision=jax.lax.Precision.HIGHEST)
    y = y.at[:, traits].add((beta[:, None] * g).T)
    return packed, y, cov


def make_cohort(config: dict, traffic: dict, seed: int) -> Cohort:
    ss = seed_sequence(seed)
    rng = np.random.default_rng(ss)
    n, p, c = config["n_samples"], traffic["n_traits"], config["n_covariates"]
    distinct, segment = config["distinct_markers"], config["scan"]["batch_markers"]
    if distinct % segment:
        raise ValueError("distinct_markers must be a multiple of batch_markers")
    rows, traits = plant(rng, n_traits=p, distinct=distinct, segment=segment,
                         density=traffic["hit_density"])
    r2 = traffic["effect_r2"]
    beta = np.sqrt(r2 / (1.0 - r2)) * rng.choice([-1.0, 1.0], size=len(rows))
    key = jax.random.key(int(ss.generate_state(1, np.uint32)[0]))
    packed, y, cov = _generate(
        key, jnp.asarray(rows), jnp.asarray(traits), jnp.asarray(beta, jnp.float32),
        n=n, p=p, c=c, distinct=distinct, segment=segment,
        maf_range=tuple(config["maf_range"]), missing_rate=float(config["missing_rate"]),
        loading_sd=float(traffic["covariate_loading_sd"]),
    )
    return Cohort(pool=np.asarray(packed), phenotypes=np.asarray(y), covariates=np.asarray(cov))
