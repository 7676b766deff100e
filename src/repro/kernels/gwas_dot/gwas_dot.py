"""Fused 2-bit-decode + standardize + GEMM Pallas TPU kernel.

The TPU-native reformulation of the paper's cuBLAS fp32 engine (DESIGN.md §5):
genotypes stay 2-bit packed in HBM exactly as they live on disk; each VMEM
tile is unpacked (shift/mask), mapped code->dosage, standardized with the
per-marker (mu, 1/sigma), missing->0, and fed to the MXU — a 16x reduction in
genotype HBM traffic versus the fp32 decode-then-GEMM the GPU release does.

Packed layout contract (produced by ``ops.pack_tiled``): samples are tiled in
groups of ``block_n``; within a tile, byte ``b`` holds the codes of samples
``{tile_start + s*block_n/4 + b : s in 0..3}`` at 2-bit slot ``s`` (LSB
first).  Unpacking is then four shift/mask ops plus one lane-concat — no
in-register transpose, which Mosaic would otherwise have to synthesize.

Grid: ``(M/bm, P/bp, N/bn)`` with the reduction axis minor (innermost), so
each output tile stays resident in VMEM across the whole contraction and the
t-statistic epilogue (paper Eq. 3) is applied in-register on the final step —
the correlation tile never round-trips through HBM between GEMM and epilogue.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["gwas_dot_kernel", "build_gwas_dot"]

# PLINK 2-bit code -> dosage for codes {0b00, 0b10, 0b11}: v = 2 - c + (c >> 1)
# (code 0b01 = missing is masked to 0 after standardization).


def gwas_dot_kernel(
    packed_ref,    # (bm, bn // 4) uint8, tile-local interleaved layout
    mean_ref,      # (bm, 1) f32
    inv_std_ref,   # (bm, 1) f32
    y_ref,         # (bn, bp) f32
    r_ref,         # (bm, bp) f32 out: correlation
    t_ref,         # (bm, bp) f32 out: t statistic
    acc_ref,       # (bm, bp) f32 scratch accumulator
    *,
    n_samples: float,
    dof: float,
    eps: float,
    n_grid: int,
    input_dtype,
    precision,
):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    codes = packed_ref[...].astype(jnp.int32)
    d0 = codes & 3
    d1 = (codes >> 2) & 3
    d2 = (codes >> 4) & 3
    d3 = (codes >> 6) & 3
    c = jnp.concatenate([d0, d1, d2, d3], axis=1)          # (bm, bn)
    dosage = (2 - c + (c >> 1)).astype(jnp.float32)
    g = (dosage - mean_ref[...]) * inv_std_ref[...]
    g = jnp.where(c == 1, 0.0, g)                          # missing -> 0 (post-standardize mean)
    acc_ref[...] += jax.lax.dot(
        g.astype(input_dtype),
        y_ref[...].astype(input_dtype),
        precision=precision,
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == n_grid - 1)
    def _epilogue():
        r = acc_ref[...] / n_samples
        r = jnp.clip(r, -1.0, 1.0)
        denom = jnp.maximum(1.0 - r * r, eps)
        r_ref[...] = r
        t_ref[...] = r * jax.lax.rsqrt(denom / dof)


def build_gwas_dot(
    m: int,
    n: int,
    p: int,
    *,
    block_m: int = 256,
    block_n: int = 512,
    block_p: int = 256,
    n_samples: float,
    dof: float,
    eps: float = 1e-12,
    input_dtype=jnp.float32,
    interpret: bool = False,
):
    """Construct the pallas_call for padded problem sizes (m, n, p).

    All of (m, n, p) must already be multiples of the block sizes; the ops
    wrapper owns padding.  ``n_samples``/``dof`` are baked in as compile-time
    constants (they are per-scan, not per-batch).
    """
    if m % block_m or n % block_n or p % block_p:
        raise ValueError(f"unpadded dims ({m},{n},{p}) vs blocks ({block_m},{block_n},{block_p})")
    if block_n % 4:
        raise ValueError("block_n must be a multiple of 4 (2-bit packing)")
    # "fp32" means an f32 GEMM, as the dense engine's HIGHEST dot does.
    # Mosaic's default contracts f32 in one bf16 pass: on a v5e chip at
    # N=23,000 it left max |dr| 8.1e-5 against float64, HIGHEST 1.0e-8 at
    # 2.4x the kernel time (PERF.md).
    precision = (
        jax.lax.Precision.HIGHEST if jnp.dtype(input_dtype) == jnp.float32
        else jax.lax.Precision.DEFAULT
    )
    grid = (m // block_m, p // block_p, n // block_n)
    kernel = functools.partial(
        gwas_dot_kernel,
        n_samples=float(n_samples),
        dof=float(dof),
        eps=float(eps),
        n_grid=grid[2],
        input_dtype=input_dtype,
        precision=precision,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_n // 4), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_m, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((block_m, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((block_n, block_p), lambda i, j, k: (k, j)),
        ],
        out_specs=[
            pl.BlockSpec((block_m, block_p), lambda i, j, k: (i, j)),
            pl.BlockSpec((block_m, block_p), lambda i, j, k: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, p), jnp.float32),
            jax.ShapeDtypeStruct((m, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_m, block_p), jnp.float32)],
        interpret=interpret,
        name="gwas_dot",
    )
