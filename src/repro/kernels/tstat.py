"""Standalone elementwise t-statistic Pallas kernel (paper Eq. 3).

The production scan uses the epilogue fused inside ``gwas_dot``; this kernel
serves the non-fused path (e.g. BGEN float dosages where the GEMM runs in
plain XLA) and doubles as the minimal worked example of the repo's kernel
conventions: kernel body + jit'd wrapper + pure-jnp ``ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import pallas_interpret

__all__ = ["screen_compact", "tstat", "tstat_ref"]


def _tstat_kernel(r_ref, t_ref, *, dof: float, eps: float):
    r = jnp.clip(r_ref[...], -1.0, 1.0)
    denom = jnp.maximum(1.0 - r * r, eps)
    t_ref[...] = r * jax.lax.rsqrt(denom / dof)


def tstat_ref(r: jax.Array, dof: float, *, eps: float = 1e-12) -> jax.Array:
    r = jnp.clip(jnp.asarray(r, jnp.float32), -1.0, 1.0)
    return r * jnp.sqrt(dof / jnp.maximum(1.0 - r * r, eps))


@functools.partial(jax.jit, static_argnames=("dof", "block_m", "block_p", "interpret"))
def _tstat_padded(r, *, dof, block_m, block_p, interpret):
    m, p = r.shape
    return pl.pallas_call(
        functools.partial(_tstat_kernel, dof=float(dof), eps=1e-12),
        grid=(m // block_m, p // block_p),
        in_specs=[pl.BlockSpec((block_m, block_p), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((block_m, block_p), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, p), jnp.float32),
        interpret=interpret,
    )(r)


def tstat(
    r: jax.Array,
    dof: float,
    *,
    block_m: int = 256,
    block_p: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """Elementwise ``T = R * sqrt(dof / (1 - R^2))`` over an ``(M, P)`` tile."""
    if interpret is None:
        interpret = pallas_interpret()
    r = jnp.asarray(r, jnp.float32)
    m_true, p_true = r.shape
    pad_m = (-m_true) % block_m
    pad_p = (-p_true) % block_p
    r_pad = jnp.pad(r, ((0, pad_m), (0, pad_p)))
    t = _tstat_padded(
        r_pad, dof=float(dof), block_m=block_m, block_p=block_p, interpret=bool(interpret)
    )
    return t[:m_true, :p_true]


_COUNT_TILE = (8, 128)  # smallest int32 block Mosaic accepts


def _screen_kernel(r_ref, t_ref, mask_ref, count_ref, *, dof: float,
                   t2_screen: float, eps: float):
    # Same arithmetic as _tstat_kernel, op for op: the sparse epilogue's t
    # tile must be bitwise-identical to the dense fused path's.
    r = jnp.clip(r_ref[...], -1.0, 1.0)
    denom = jnp.maximum(1.0 - r * r, eps)
    t = r * jax.lax.rsqrt(denom / dof)
    t_ref[...] = t
    keep = t * t >= t2_screen
    mask_ref[...] = keep.astype(jnp.int8)
    # The block's survivor count, broadcast over one (8, 128) int32 tile:
    # Mosaic needs the last two block dims to be multiples of (8, 128), so a
    # per-block scalar output is not expressible; the wrapper reads one lane.
    count_ref[...] = jnp.full(count_ref.shape, jnp.sum(keep.astype(jnp.int32)))


@functools.partial(
    jax.jit, static_argnames=("dof", "t2_screen", "block_m", "block_p", "interpret")
)
def _screen_padded(r, *, dof, t2_screen, block_m, block_p, interpret):
    m, p = r.shape
    gm, gp = m // block_m, p // block_p
    return pl.pallas_call(
        functools.partial(
            _screen_kernel, dof=float(dof), t2_screen=float(t2_screen), eps=1e-12
        ),
        grid=(gm, gp),
        in_specs=[pl.BlockSpec((block_m, block_p), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((block_m, block_p), lambda i, j: (i, j)),
            pl.BlockSpec((block_m, block_p), lambda i, j: (i, j)),
            pl.BlockSpec(_COUNT_TILE, lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, p), jnp.float32),
            jax.ShapeDtypeStruct((m, p), jnp.int8),
            jax.ShapeDtypeStruct((gm * _COUNT_TILE[0], gp * _COUNT_TILE[1]), jnp.int32),
        ],
        interpret=interpret,
    )(r)


def screen_compact(
    r: jax.Array,
    dof: float,
    t2_screen: float,
    capacity: int,
    *,
    block_m: int = 256,
    block_p: int = 256,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused t-statistic + ``t^2 >= t2_screen`` survivor screen (DESIGN.md 13).

    One Pallas pass emits the t tile, a survivor mask, and per-block survivor
    counts; the wrapper then compacts survivor *flat indices* (row-major over
    the unpadded tile, dense ``np.nonzero`` order) into a fixed ``capacity``
    buffer with XLA's sized ``nonzero`` — true in-kernel compaction would need
    a scatter/sort the TPU lacks a cheap lowering for, so only the screen and
    the reduction fuse into the kernel. Returns ``(t, hit_idx, screen_count)``
    where ``hit_idx`` pads exhausted slots with ``-1`` and ``screen_count`` is
    the exact survivor total (trustworthy even when ``> capacity``).

    ``t2_screen`` must be positive: padding lanes carry ``r = 0 -> t = 0`` and
    must never survive the screen.
    """
    if interpret is None:
        interpret = pallas_interpret()
    r = jnp.asarray(r, jnp.float32)
    m_true, p_true = r.shape
    pad_m = (-m_true) % block_m
    pad_p = (-p_true) % block_p
    r_pad = jnp.pad(r, ((0, pad_m), (0, pad_p)))
    t, mask, counts = _screen_padded(
        r_pad, dof=float(dof), t2_screen=float(t2_screen),
        block_m=block_m, block_p=block_p, interpret=bool(interpret),
    )
    keep = mask[:m_true, :p_true].ravel() != 0
    idx = jnp.nonzero(keep, size=int(capacity), fill_value=-1)[0].astype(jnp.int32)
    per_block = counts[:: _COUNT_TILE[0], :: _COUNT_TILE[1]]
    return t[:m_true, :p_true], idx, jnp.sum(per_block).astype(jnp.int32)
