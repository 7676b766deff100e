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

from repro.core.association import compact_survivors
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
        name="gwas_tstat",
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


def _screen_kernel(r_ref, t_ref, mask_ref, *, dof: float, t2_screen: float,
                   eps: float):
    # Same arithmetic as _tstat_kernel, op for op: the sparse epilogue's t
    # tile must be bitwise-identical to the dense fused path's.
    r = jnp.clip(r_ref[...], -1.0, 1.0)
    denom = jnp.maximum(1.0 - r * r, eps)
    t = r * jax.lax.rsqrt(denom / dof)
    t_ref[...] = t
    mask_ref[...] = (t * t >= t2_screen).astype(jnp.int8)


@functools.partial(
    jax.jit, static_argnames=("dof", "t2_screen", "block_m", "block_p", "interpret")
)
def _screen_padded(r, *, dof, t2_screen, block_m, block_p, interpret):
    m, p = r.shape
    return pl.pallas_call(
        functools.partial(
            _screen_kernel, dof=float(dof), t2_screen=float(t2_screen), eps=1e-12
        ),
        grid=(m // block_m, p // block_p),
        in_specs=[pl.BlockSpec((block_m, block_p), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((block_m, block_p), lambda i, j: (i, j)),
            pl.BlockSpec((block_m, block_p), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, p), jnp.float32),
            jax.ShapeDtypeStruct((m, p), jnp.int8),
        ],
        interpret=interpret,
        name="gwas_screen_compact",
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

    One Pallas pass emits the t tile and a survivor mask; the wrapper then
    compacts survivor *flat indices* (row-major over the unpadded tile, dense
    ``np.nonzero`` order) into a fixed ``capacity`` buffer with
    ``core.association.compact_survivors``, the compaction the XLA epilogue
    uses too. Returns ``(t, hit_idx, screen_count)`` where ``hit_idx`` pads
    exhausted slots with ``-1`` and ``screen_count`` is the exact survivor
    total (trustworthy even when ``> capacity``).

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
    t, mask = _screen_padded(
        r_pad, dof=float(dof), t2_screen=float(t2_screen),
        block_m=block_m, block_p=block_p, interpret=bool(interpret),
    )
    idx, screen_count = compact_survivors(mask[:m_true, :p_true] != 0, int(capacity))
    return t[:m_true, :p_true], idx, screen_count
