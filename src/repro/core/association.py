"""The TorchGWAS association kernel (paper §2.2) as a composable JAX module.

The hot path is one GEMM per genotype batch:

    R = G_std @ Y_std / N          (Eq. 2)   G_std: (M, N), Y_std: (N, P)
    T = R * sqrt(dof / (1 - R^2))  (Eq. 3)
    p = two-sided t tail           (core.stats, log-space)

Everything is a pure function of arrays so it jits/shards cleanly.  The
distribution contract (see launch/mesh.py):

    marker-sharded mode ("mp"):   G: P(('pod','data'), None)   Y: P(None, 'model')
                                  R/T/p: P(('pod','data'), 'model')  — no collectives
    sample-sharded mode ("sample"): G: P(None, ('pod','data'))  Y: P(('pod','data'), 'model')
                                  R: psum over 'data' (XLA inserts the all-reduce)

Precision ladder (paper-faithful first):
    "fp32"  — float32 inputs, HIGHEST precision dot (paper: cuBLAS fp32)
    "bf16"  — bfloat16 inputs, float32 accumulation (TPU MXU native; beyond-paper)
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import stats as _stats

__all__ = [
    "AssocOptions",
    "MarkerStats",
    "AssocResult",
    "SparseEpilogue",
    "standardize_genotype_batch",
    "correlation",
    "assoc_from_standardized",
    "assoc_batch",
    "plan_sparse_epilogue",
    "compact_survivors",
    "sparse_epilogue_outputs",
]


@dataclasses.dataclass(frozen=True)
class AssocOptions:
    """Options for the association engine.

    dof_mode: "paper" uses N-2 (Eq. 3 as published); "exact" uses N-2-q and
        implies genotype residualization (Frisch-Waugh-Lovell) so the result
        equals full covariate-adjusted OLS.
    precision: "fp32" | "bf16" (see module docstring).
    eps: clamp for 1 - r^2.
    compute_neglog10p: skip the (elementwise but special-function-heavy)
        p-value epilogue when only |T| ranking is needed.
    sparse_epilogue: sparse p-value mode (DESIGN.md §13): skip the full
        (M, P) -log10 p tile — the caller screens on t^2 and refines only
        past-threshold lanes through ``sparse_epilogue_outputs``.  Implies
        the nlp tile of ``AssocResult`` is zeros, like
        ``compute_neglog10p=False``.
    """

    dof_mode: str = "paper"
    precision: str = "fp32"
    eps: float = 1e-12
    compute_neglog10p: bool = True
    sparse_epilogue: bool = False

    def __post_init__(self) -> None:
        if self.dof_mode not in ("paper", "exact"):
            raise ValueError(f"unknown dof_mode: {self.dof_mode!r}")
        if self.precision not in ("fp32", "bf16"):
            raise ValueError(f"unknown precision: {self.precision!r}")

    def dof(self, n_samples: int, n_covariates: int) -> int:
        if self.dof_mode == "paper":
            return n_samples - 2
        return n_samples - 2 - n_covariates


class MarkerStats(NamedTuple):
    """Per-marker summary statistics from standardization."""

    mean: jax.Array       # (M,) dosage mean over non-missing samples
    inv_std: jax.Array    # (M,) 1/population-std of the imputed dosage; 0 if monomorphic
    maf: jax.Array        # (M,) minor-allele frequency
    n_missing: jax.Array  # (M,) int32
    valid: jax.Array      # (M,) bool — polymorphic and not all-missing


class AssocResult(NamedTuple):
    r: jax.Array            # (M, P) correlation
    t: jax.Array            # (M, P) t statistic
    neglog10p: jax.Array    # (M, P) two-sided -log10 p (zeros if disabled)


def standardize_genotype_batch(
    g_raw: jax.Array,
    *,
    missing_value: float = -9.0,
    var_tol: float = 1e-10,
) -> tuple[jax.Array, MarkerStats]:
    """Standardize a dosage batch ``(M, N)``; missing entries are mean-imputed.

    ``missing_value`` marks missing dosages (NaN also works).  The imputed
    value is the per-marker mean, which becomes exactly 0 after
    standardization — this is what lets the fused 2-bit kernel map the
    missing code straight to 0.
    """
    g = jnp.asarray(g_raw, jnp.float32)
    missing = jnp.isnan(g) | (g == missing_value)
    present = ~missing
    n_present = jnp.maximum(jnp.sum(present, axis=1), 1)
    mean = jnp.sum(jnp.where(present, g, 0.0), axis=1) / n_present
    g_imp = jnp.where(present, g, mean[:, None])
    var = jnp.mean(jnp.square(g_imp - mean[:, None]), axis=1)
    valid = (var > var_tol) & (jnp.sum(present, axis=1) > 0)
    inv_std = jnp.where(valid, jax.lax.rsqrt(jnp.maximum(var, var_tol)), 0.0)
    g_std = (g_imp - mean[:, None]) * inv_std[:, None]
    af = mean / 2.0
    maf = jnp.minimum(af, 1.0 - af)
    return g_std, MarkerStats(
        mean=mean,
        inv_std=inv_std,
        maf=maf,
        n_missing=jnp.sum(missing, axis=1).astype(jnp.int32),
        valid=valid,
    )


def correlation(
    g_std: jax.Array,
    y_std: jax.Array,
    n_samples: int | jax.Array,
    *,
    precision: str = "fp32",
    trait_tile: int | None = None,
) -> jax.Array:
    """Paper Eq. (2): ``R = G Y / N`` with an explicit precision contract.

    ``trait_tile`` fixes the panel-axis compute tile: the GEMM is evaluated
    in ``trait_tile``-wide column chunks (last chunk ragged) instead of one
    panel-wide dot.  This is the same discipline the fused Pallas kernel
    applies with ``block_p``, and it is what makes the blocked 2-D scan grid
    bitwise-identical to the unblocked scan (DESIGN.md §10): BLAS/XLA GEMM
    micro-kernels group accumulators differently per output width, so the
    only way two decompositions of the trait axis agree bitwise is to run
    the *same* fixed-width tiles in both.  ``None`` keeps the single-dot
    behavior (standalone use; the scan always passes its ``block_p``).
    """
    if precision == "bf16":
        g_std = g_std.astype(jnp.bfloat16)
        y_std = y_std.astype(jnp.bfloat16)
        dot_precision = jax.lax.Precision.DEFAULT
    else:
        dot_precision = jax.lax.Precision.HIGHEST

    def dot(y_cols: jax.Array) -> jax.Array:
        return jax.lax.dot_general(
            g_std,
            y_cols,
            (((1,), (0,)), ((), ())),
            precision=dot_precision,
            preferred_element_type=jnp.float32,
        )

    p = y_std.shape[1]
    if trait_tile is not None and 0 < trait_tile < p:
        r = jnp.concatenate(
            [dot(y_std[:, i : i + trait_tile]) for i in range(0, p, trait_tile)],
            axis=1,
        )
    else:
        r = dot(y_std)
    return r / jnp.asarray(n_samples, jnp.float32)


def assoc_from_standardized(
    g_std: jax.Array,
    y_std: jax.Array,
    *,
    n_samples: int,
    n_covariates: int,
    options: AssocOptions = AssocOptions(),
    trait_tile: int | None = None,
) -> AssocResult:
    """Association statistics from pre-standardized inputs (both zero-mean,
    unit population variance).  This is the function the distributed scan
    jits; shapes ``(M, N) x (N, P) -> (M, P)``.  ``trait_tile`` — see
    ``correlation``.  The GEMM and t run under the ``gwas.assoc`` scope,
    the dense -log10 p tile under ``gwas.epilogue``."""
    dof = options.dof(n_samples, n_covariates)
    with jax.named_scope("gwas.assoc"):
        r = correlation(
            g_std, y_std, n_samples, precision=options.precision,
            trait_tile=trait_tile,
        )
        # Guard: standardization guarantees |r| <= 1 up to rounding; clamp
        # so the epilogue stays finite even for degenerate columns.
        r = jnp.clip(r, -1.0, 1.0)
        t = _stats.t_from_r(r, dof, eps=options.eps)
    with jax.named_scope("gwas.epilogue"):
        if options.compute_neglog10p and not options.sparse_epilogue:
            nlp = _stats.neglog10_p_from_t(t, dof)
        else:
            nlp = jnp.zeros_like(t)
    return AssocResult(r=r, t=t, neglog10p=nlp)


def assoc_batch(
    g_raw: jax.Array,
    y_std: jax.Array,
    *,
    n_samples: int,
    n_covariates: int,
    options: AssocOptions = AssocOptions(),
    q_basis: jax.Array | None = None,
    missing_value: float = -9.0,
) -> tuple[AssocResult, MarkerStats]:
    """End-to-end batch path from raw dosages: standardize -> (optionally
    FWL-residualize) -> correlate -> epilogue.

    ``q_basis`` is required when ``options.dof_mode == "exact"``.
    """
    g_std, marker_stats = standardize_genotype_batch(g_raw, missing_value=missing_value)
    if options.dof_mode == "exact":
        if q_basis is None:
            raise ValueError("exact mode requires the covariate basis q_basis")
        from repro.core.residualize import residualize_genotypes

        g_std = residualize_genotypes(g_std, q_basis)
    res = assoc_from_standardized(
        g_std,
        y_std,
        n_samples=n_samples,
        n_covariates=n_covariates,
        options=options,
    )
    # Invalid (monomorphic / all-missing) markers: r=t=0, p=1.
    mask = marker_stats.valid[:, None]
    res = AssocResult(
        r=jnp.where(mask, res.r, 0.0),
        t=jnp.where(mask, res.t, 0.0),
        neglog10p=jnp.where(mask, res.neglog10p, 0.0),
    )
    return res, marker_stats


# ----------------------------------------------------- sparse p-value epilogue
#
# DESIGN.md §13.  The 128-trip Lentz continued fraction in
# ``stats.neglog10_p_from_t`` dominated the full scan (BENCH_scan.json
# measured a 0.94-0.99 epilogue share) because it ran over every lane of
# every (M, P) tile.  For fixed dof, -log10 p is strictly monotone in t^2,
# so the epilogue only needs the CF on (a) the per-trait t^2 winner and
# (b) the lanes past a conservative t^2 screen — O(P + hits) evaluations
# instead of O(M*P), with bitwise-identical results.


@dataclasses.dataclass(frozen=True)
class SparseEpilogue:
    """Per-scan compile-time constants of the sparse p-value epilogue.

    ``t2_screen`` is the conservative inverse of the hit threshold
    (``stats.t2_screen_threshold``); ``capacity`` the static size of the
    compacted device buffer (jit shapes stay fixed — past-capacity cells
    overflow to the host fallback in ``core.sinks.extract_hits``).
    """

    threshold_nlp: float
    t2_screen: float
    capacity: int


def plan_sparse_epilogue(
    threshold_nlp: float,
    dof: float,
    *,
    capacity: int = 4096,
    cell_area: int | None = None,
) -> SparseEpilogue | None:
    """Resolve the sparse-epilogue constants for one scan, or ``None`` when
    screening cannot help (threshold at/below the inversion margin, or a
    non-positive dof).  ``cell_area`` clamps the compacted buffer at the
    grid-cell extent — a buffer wider than the tile it compacts is waste.
    """
    t2 = _stats.t2_screen_threshold(float(threshold_nlp), float(dof))
    if t2 is None or not (t2 > 0.0):
        return None
    cap = int(capacity)
    if cell_area is not None:
        cap = min(cap, int(cell_area))
    # Round up to a multiple of the canonical refine chunk width so the
    # compacted buffer's slot layout chunks evenly — a survivor then lands
    # in the same (REFINE_WIDTH,) chunk slot whether it came off the
    # device compact buffer or the host survivor gather (DESIGN.md §13).
    w = _stats.REFINE_WIDTH
    cap = max(w, -(-cap // w) * w)
    return SparseEpilogue(float(threshold_nlp), float(t2), cap)


_CHUNK = 128  # lanes per chunk of compact_survivors: one TPU vector row


def compact_survivors(keep: jax.Array, capacity: int) -> tuple[jax.Array, jax.Array]:
    """First-K compaction of a boolean screen without a scatter.

    Returns ``(hit_idx, screen_count)``: ``hit_idx`` (capacity,) int32 holds
    the row-major flat indices of the first ``capacity`` true lanes in
    ascending order, -1 padded — bitwise ``jnp.nonzero(keep.ravel(),
    size=capacity, fill_value=-1)[0]`` — and ``screen_count`` () int32 is the
    exact number of true lanes, even past ``capacity``.

    ``nonzero`` lowers to a full-length cumsum plus a scatter-add of every
    lane into ``capacity`` bins, which the TPU serialises.  Here the flat
    screen is cut into 128-lane chunks (the tail zero-padded) and reduced to
    one count per chunk; a binary search of the counts' inclusive cumsum
    finds the chunk holding each output slot's survivor, and a cumsum over
    only those ``capacity`` gathered chunks finds its lane.  The cost does
    not depend on how many lanes survive (DESIGN.md §13).
    """
    flat = keep.ravel()
    n_chunks = -(-flat.size // _CHUNK)
    chunks = jnp.pad(flat, (0, n_chunks * _CHUNK - flat.size)).reshape(n_chunks, _CHUNK)
    counts = jnp.sum(chunks, axis=1, dtype=jnp.int32)
    ends = jnp.cumsum(counts)                      # survivors up to and with chunk j
    screen_count = ends[-1]
    k = jnp.arange(capacity, dtype=jnp.int32)      # output slot = survivor rank
    chunk = jnp.searchsorted(ends, k, side="right", method="scan_unrolled")
    chunk = jnp.minimum(chunk, n_chunks - 1).astype(jnp.int32)
    rank = k - (ends[chunk] - counts[chunk])       # the survivor's rank in its chunk
    running = jnp.cumsum(chunks[chunk], axis=1, dtype=jnp.int32)
    lane = jnp.sum(running <= rank[:, None], axis=1, dtype=jnp.int32)
    idx = jnp.where(k < screen_count, chunk * _CHUNK + lane, -1)
    return idx, screen_count


def sparse_epilogue_outputs(
    r: jax.Array,
    t: jax.Array,
    dof: float,
    plan: SparseEpilogue,
    *,
    screen: tuple[jax.Array, jax.Array] | None = None,
) -> dict[str, jax.Array]:
    """Screen one masked (M, P) statistic tile on t^2 and compact survivors.

    Inputs must be the *masked* r/t tiles (invalid lanes zeroed) so masked
    lanes never pass the screen.  No CF runs here at all: the exact-tail
    refine happens host-side through the canonical per-(shape, dof)
    executables (``stats.refine_neglog10p``) so the sparse, dense-audit,
    and overflow paths all evaluate -log10 p in one compiled program —
    in-step CF bits are fusion-context-sensitive and would break the
    bitwise contract (DESIGN.md §13).  Returns the sparse step outputs:

        batch_best_row   (P,) int32 — argmax over t^2 (first index on ties;
                         identical to argmax over the nlp tile because nlp
                         is a monotone function of t^2 — the §13 contract)
        batch_best_t     (P,) f32 — winner t, refined host-side
        hit_idx          (capacity,) int32 — row-major flat indices of
                         screened lanes in first-K order (matches the dense
                         path's np.nonzero order), -1 padded
        hit_r/hit_t      (capacity,) f32 — gathered stats; 0 in padding
        screen_count     () int32 — total screened lanes; > capacity means
                         the buffer overflowed (host fallback)

    ``screen`` optionally supplies ``(hit_idx, screen_count)`` from the fused
    screen kernel (``kernels.tstat.screen_compact``); both compact through
    ``compact_survivors``, so the layout is identical either way.  With no
    survivors every slot is -1.

    Runs under the ``gwas.epilogue`` scope: the winners under
    ``gwas.epilogue.best``, the screen and compaction under
    ``gwas.epilogue.compact``.
    """
    del dof  # the refine is host-side now; kept for call-site symmetry
    with jax.named_scope("gwas.epilogue"):
        t2 = jnp.square(t)
        with jax.named_scope("gwas.epilogue.best"):
            # argmax over the transposed tile: per-trait reductions then run
            # along contiguous memory (~1.7x faster on XLA CPU) and the
            # result is the same int32 — argmax keeps first-occurrence ties
            # along the marker axis in either layout.
            best_row = jnp.argmax(t2.T, axis=1).astype(jnp.int32)
            best_t = jnp.take_along_axis(t, best_row[None, :], axis=0)[0]
        with jax.named_scope("gwas.epilogue.compact"):
            if screen is None:
                idx, screen_count = compact_survivors(t2 >= plan.t2_screen, plan.capacity)
            else:
                idx, screen_count = screen
            slot = idx >= 0
            # (row, col) gathers read the tiles in place; a gather from the
            # flat view would relayout both whole tiles first on the TPU.
            row, col = jnp.divmod(jnp.maximum(idx, 0), t.shape[1])
            hit_t = jnp.where(slot, t[row, col], 0.0)
            hit_r = jnp.where(slot, r[row, col], 0.0)
    return {
        "batch_best_row": best_row,
        "batch_best_t": best_t,
        "hit_idx": idx,
        "hit_r": hit_r,
        "hit_t": hit_t,
        "screen_count": screen_count,
    }
