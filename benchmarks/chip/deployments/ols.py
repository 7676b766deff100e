"""The paper's deployment: unrelated samples, OLS with dof N - 2.

The module a configuration runs when it names none.  It makes the cell's
data, binds and plans it, holds the answers to the plain reference, and
counts the work, through the functions ``harness.load_deployment``
documents.

Data.  A pool of distinct markers (PLINK 2-bit bytes, MAF uniform on the
configured range, missing calls at the configured rate, as
``repro.io.synth.make_cohort`` draws them), a covariate matrix, and a
phenotype panel: unit-variance noise, covariate loadings, and planted
effects, made from ``--seed`` on the device in one jitted call.  Every
seed plants the same number of (marker, trait) pairs, spread evenly over
the pool's batch-sized segments, so every cell of every seed carries the
same number of hits; the seed changes which ones.  The genome recycles
the pool (``genome.VirtualGenome``): marker ``i`` is pool row
``i mod pool_markers``.

Reference.  The paper's OLS scan (Eq. 1-3) in float64 on the host.  It
reads only the cell's data and imports nothing of the program.
Covariates are centred, scaled and projected out of each phenotype with
an intercept (Eq. 1); phenotypes and genotypes are standardized to unit
population variance, a missing call taking the marker's mean;
``r = g . y / N``, ``t = r sqrt(dof / (1 - r^2))`` with ``dof = N - 2``
(the paper's Eq. 3), and -log10 p is the two-sided Student-t tail.  It is
asked about genome markers and maps each to its pool row itself.

Control.  ``LowerPrecision``: the same arithmetic with the dot in three
bf16 passes (TPU ``Precision.HIGH``, the step below the configured
``HIGHEST``) and t and -log10 p rounded to bfloat16 (the step below plain
float32), put in the program's place.

Work.  The statistic the paper computes, not whatever implements it:
``2 M N P`` operations for M markers, N real samples (padding is waste,
not work) and P traits; bytes are the packed genotypes ``M ceil(N/4)``
plus one read of the float32 panel ``4 N P`` per marker-batch sweep.  The
rate is the chip's highest published matrix-unit rate (int8): genotypes
are exact small integers, so no implementation of this work, int8 limbs
included, can beat it.
"""
from __future__ import annotations

import functools
import os
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from scipy import stats as sps

from cohort import plant, seed_sequence
from genome import VirtualGenome, decode

# ------------------------------------------------------------------ data


@dataclass
class Cohort:
    pool: np.ndarray            # (distinct_markers, ceil(N/4)) uint8, PLINK bytes
    phenotypes: np.ndarray      # (N, P) float32
    covariates: np.ndarray      # (N, C) float32


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


# ---------------------------------------------------------- bind and plan


def bind(cohort: Cohort, config: dict):
    """The cohort over a ``VirtualGenome``, bound with ``Study.from_arrays``."""
    from repro.api import Study

    source = VirtualGenome(cohort.pool, config["n_samples"], config["n_markers"])
    return Study.from_arrays(source, cohort.phenotypes, cohort.covariates)


def plan_kwargs(scan: dict, spill_dir: str) -> dict:
    from repro.api import ExecSpec, GridSpec, IOSpec
    from repro.core.association import AssocOptions

    return dict(
        engine=scan["engine"],
        grid=GridSpec(batch_markers=scan["batch_markers"], trait_block=scan["trait_block"],
                      block_m=scan["block_m"], block_n=scan["block_n"],
                      block_p=scan["block_p"],
                      panel_resident_blocks=scan["panel_resident_blocks"]),
        io=IOSpec(prefetch_depth=scan["prefetch_depth"], io_workers=scan["io_workers"],
                  spill_dir=spill_dir, hit_spill_rows=scan["hit_spill_rows"],
                  genotype_staging=scan["genotype_staging"],
                  packed_cache_mb=scan["packed_cache_mb"]),
        executor=ExecSpec(devices=scan["devices"], placement=scan["placement"],
                          lease_batches=scan["lease_batches"],
                          slot_prefetch=scan["slot_prefetch"],
                          autotune_lease=scan["autotune_lease"]),
        options=AssocOptions(dof_mode=scan["dof_mode"], precision=scan["precision"]),
        hit_threshold_nlp=scan["hit_threshold_nlp"],
        input_dtype=scan["input_dtype"],
        sparse_epilogue=scan["sparse_epilogue"],
        hit_capacity=scan["hit_capacity"],
    )


# -------------------------------------------------------------- reference

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
        self._blocks: dict[tuple[int, int], np.ndarray] = {}   # see r_block
        self._blocks_of: np.ndarray | None = None

    def pool_rows(self, markers: np.ndarray) -> np.ndarray:
        """The pool row of each genome marker."""
        return np.asarray(markers, np.int64) % self.pool.shape[0]

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

    def r_block(self, markers: np.ndarray, y: np.ndarray) -> np.ndarray:
        """``(len(markers), k)`` correlations of genome ``markers`` with panel
        ``y``.  Genome ranges that recycle the same pool rows answer alike,
        so each run of rows (first row, length) is worked out once per panel."""
        rows = self.pool_rows(markers)
        if self._blocks_of is not y:
            self._blocks, self._blocks_of = {}, y
        if not len(rows) or np.any(np.diff(rows) != 1):
            return self._r_rows(rows, y)
        key = (int(rows[0]), len(rows))
        if key not in self._blocks:
            self._blocks[key] = self._r_rows(rows, y)
        return self._blocks[key]

    def _r_rows(self, rows: np.ndarray, y: np.ndarray) -> np.ndarray:
        if not len(rows):
            return np.zeros((0, y.shape[1]))
        return np.concatenate(_chunks(lambda a, b: self.genotypes(rows[a:b]) @ y / self.n,
                                      len(rows)))

    def r_pairs(self, markers: np.ndarray, traits: np.ndarray) -> np.ndarray:
        """``r`` of each (genome marker, trait) pair, worked out once for
        each distinct (pool row, trait)."""
        pairs, inverse = np.unique(np.stack([self.pool_rows(markers), traits], 1), axis=0,
                                   return_inverse=True)
        rows, traits = pairs[:, 0], pairs[:, 1]
        order = np.argsort(rows, kind="stable")

        def some(a, b):
            sel = order[a:b]
            return np.einsum("kn,nk->k", self.genotypes(rows[sel]), self.panel(traits[sel]))

        out = np.empty(len(rows))
        if len(rows):
            out[order] = np.concatenate(_chunks(some, len(rows))) / self.n
        return out[inverse.ravel()]

    def t(self, r: np.ndarray) -> np.ndarray:
        return r * np.sqrt(self.dof / np.maximum(1.0 - r * r, 1e-300))

    def nlp(self, t: np.ndarray) -> np.ndarray:
        return -(sps.t.logsf(np.abs(t), self.dof) + np.log(2.0)) / np.log(10.0)


def _bf16(x: np.ndarray) -> np.ndarray:
    return np.asarray(jnp.asarray(x, jnp.float32).astype(jnp.bfloat16).astype(jnp.float32))


class LowerPrecision(Reference):
    """The reference one precision step down, in the program's place."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)

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

    def _r_rows(self, rows, y):
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


def reference(cohort: Cohort, config: dict) -> Reference:
    return Reference(cohort.pool, cohort.phenotypes, cohort.covariates, config["n_samples"])


def control(cohort: Cohort, config: dict) -> LowerPrecision:
    return LowerPrecision(cohort.pool, cohort.phenotypes, cohort.covariates,
                          config["n_samples"])


# ------------------------------------------------------------------ work


def assoc_ops(markers: int, samples: int, traits: int) -> float:
    return 2.0 * markers * samples * traits


def assoc_bytes(markers: int, samples: int, traits: int) -> float:
    return float(markers * -(-samples // 4) + 4 * samples * traits)


def least_seconds(markers: int, samples: int, traits: int, peak: dict) -> tuple[float, str]:
    """(least time, the bound that sets it: "compute" or "memory")."""
    compute = assoc_ops(markers, samples, traits) / peak["int8_ops_per_s"]
    memory = assoc_bytes(markers, samples, traits) / peak["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
