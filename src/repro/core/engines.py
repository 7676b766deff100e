"""Pluggable scan engines: device step construction + host batch preparation.

An engine owns both halves of one batch's journey (DESIGN.md §2):

    host side    ``prepare_batch``  — read from the genotype source, decode /
                 repack / compute marker stats on a prefetch worker thread,
                 returning a ``HostBatch`` of device-ready ndarrays
    device side  ``build_step``     — a jit'd (optionally sharded) callable
                 mapping those arrays + the trait panel to summary tiles

``GenomeScan`` resolves engines by name through the registry and never
branches on engine identity — new engines (e.g. an int8 dequant GEMM or a
mixed-precision screen) plug in with ``@register_engine`` and a config
string, touching no driver code.

``build_dense_step`` / ``build_fused_step`` remain importable (also re-
exported from ``core.screening``) for tests and external harnesses.
"""
from __future__ import annotations

import dataclasses
import functools
import threading
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import stats as _stats
from repro.core.association import (
    AssocOptions,
    assoc_from_standardized,
    plan_sparse_epilogue,
    sparse_epilogue_outputs,
    standardize_genotype_batch,
)
from repro.kernels import pallas_interpret
from repro.runtime.prefetch import MarkerBatch, TraitBlock
from repro.runtime.sharding import batch_axes, gwas_shardings

__all__ = [
    "EngineContext",
    "EngineDeviceState",
    "HostBatch",
    "ScanEngine",
    "DeviceLRU",
    "DenseEngine",
    "FusedEngine",
    "LMMEngine",
    "register_engine",
    "get_engine",
    "available_engines",
    "build_dense_step",
    "build_fused_step",
    "build_lmm_step",
]


class DeviceLRU:
    """Small keyed cache of device-staged arrays with LRU eviction.

    One idiom, four users (the driver's ``PanelStore`` blocks, the lmm
    engine's per-(scope, block) panels and per-scope rotation pairs, the
    serve registry's warm executor slots): stage through ``loader`` on
    miss, refresh recency on hit, evict the least recently used entry past
    ``capacity``.  ``on_evict`` lets dependent caches cascade (a LOCO
    scope's panel blocks die with its rotation).  Thread-safe: loaders may
    be reached from prefetch workers.

    ``pin``/``unpin`` hold a ref-count per key: pinned entries are never
    chosen for eviction (capacity may be transiently exceeded while every
    resident entry is pinned), which is what lets a long-lived serve
    request keep its device state resident while other requests churn the
    cache.  Hit/miss/eviction counters feed the serve cache-hit-rate
    observability and cost nothing on the scan hot path.
    """

    def __init__(self, capacity: int, loader: Callable[[Any], Any],
                 *, on_evict: Callable[[Any], None] | None = None):
        self.capacity = max(1, capacity)
        self._loader = loader
        self._on_evict = on_evict
        self._data: dict[Any, Any] = {}
        self._pins: dict[Any, int] = {}
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def get(self, key: Any) -> Any:
        with self._lock:
            if key in self._data:
                self.hits += 1
                self._data[key] = self._data.pop(key)  # refresh recency
            else:
                self.misses += 1
                while len(self._data) >= self.capacity:
                    gone = next(
                        (k for k in self._data if k not in self._pins), None
                    )
                    if gone is None:
                        break  # everything resident is pinned: overshoot
                    self._data.pop(gone)
                    self.evictions += 1
                    if self._on_evict is not None:
                        self._on_evict(gone)
                self._data[key] = self._loader(key)
            return self._data[key]

    def pin(self, key: Any) -> None:
        """Hold ``key`` resident (ref-counted): eviction skips it until the
        matching ``unpin``.  Pinning a not-yet-loaded key is allowed — the
        pin protects the entry the next ``get`` stages."""
        with self._lock:
            self._pins[key] = self._pins.get(key, 0) + 1

    def unpin(self, key: Any) -> None:
        with self._lock:
            if key not in self._pins:
                raise KeyError(f"unpin of {key!r} without a matching pin")
            n = self._pins[key] - 1
            if n <= 0:
                del self._pins[key]
            else:
                self._pins[key] = n

    def pinned(self, key: Any) -> bool:
        with self._lock:
            return key in self._pins

    @property
    def n_pinned(self) -> int:
        return len(self._pins)

    def stats(self) -> dict:
        """Counter snapshot for cache observability (serve metrics)."""
        with self._lock:
            total = self.hits + self.misses
            return {
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
                "resident": len(self._data),
                "pinned": len(self._pins),
                "hit_rate": round(self.hits / total, 4) if total else None,
            }

    def drop_if(self, pred: Callable[[Any], bool]) -> None:
        with self._lock:
            for key in [k for k in self._data if pred(k)]:
                self._data.pop(key)

    def clear(self) -> None:
        """Drop every staged entry (cascading through ``on_evict``) —
        executor-slot teardown, so a closed scan pins no device blocks.
        Deliberately ignores pins: teardown outranks residency, and the
        pin table is cleared with the data."""
        with self._lock:
            for key in list(self._data):
                self._data.pop(key)
                if self._on_evict is not None:
                    self._on_evict(key)
            self._pins.clear()

    def __len__(self) -> int:
        return len(self._data)


@dataclass
class EngineContext:
    """Everything an engine needs, assembled once per scan by the driver."""

    n_samples: int                     # after relatedness exclusion
    n_covariates: int
    options: AssocOptions
    mesh: Mesh | None = None
    mode: str = "mp"
    hit_threshold: float = 7.301
    maf_min: float = 0.0
    block_m: int = 256
    block_n: int = 512
    block_p: int = 256
    q_basis: jax.Array | None = None
    multivariate: bool = False
    n_traits_eff: float = 1.0
    whitening: jax.Array | None = None
    keep: np.ndarray | None = None     # host-side sample mask (None: keep all)
    excluded_samples: int = 0
    # the trait axis of the 2-D scan grid (DESIGN.md §10): the planned
    # blocks, and how many panel blocks an engine may keep device-resident
    trait_blocks: tuple[TraitBlock, ...] = ()
    panel_resident_blocks: int = 4
    # fused kernel GEMM input dtype ("fp32" | "bf16"); the epilogue (t,
    # -log10 p, argmax) always runs fp32 regardless (tests/test_oracle.py
    # bf16 audit)
    input_dtype: str = "fp32"
    # mixed-model knobs (consumed by the lmm engine only)
    loco: bool = False
    grm_method: str = "std"
    grm_batch_markers: int = 4096
    lmm_delta: float | None = None
    lmm_epilogue: str = "dense"
    io_workers: int = 2
    # sparse p-value epilogue (DESIGN.md §13): screen on t^2, exact-CF
    # refine only winners + past-threshold lanes.  Results are bitwise-
    # identical to the dense CF path; engines silently fall back to dense
    # under a sharding mesh (data-dependent gathers don't shard).
    sparse_epilogue: bool = False
    hit_capacity: int = 4096
    # H2D staging currency (DESIGN.md §17): "dense" stages decoded float32
    # (the historical path), "packed" stages raw PLINK 2-bit bytes and
    # decodes on device — ~16x less H2D traffic, bitwise-identical results.
    # Drivers resolve "auto"/"packed" via ``resolve_genotype_staging``
    # before building the context; engines trust the resolved value.
    genotype_staging: str = "dense"


GENOTYPE_STAGINGS = ("auto", "packed", "dense")


def resolve_genotype_staging(
    requested: str,
    source: Any,
    *,
    excluded_samples: int = 0,
    mesh: Mesh | None = None,
) -> str:
    """Negotiate the staging currency per source (DESIGN.md §17).

    "auto" picks packed whenever it is exactly equivalent and actually
    cheaper: the source speaks native 2-bit bytes (PlinkBed, MultiFileSource
    of beds — numpy/BGEN fall back decoded, unchanged), no host-side sample
    subsetting (relatedness exclusion slices the decoded matrix before
    staging), and no sharding mesh (staged shardings are declared over the
    decoded layout).  Explicit "packed" raises instead of silently falling
    back; "dense" is always honored.
    """
    if requested not in GENOTYPE_STAGINGS:
        raise ValueError(
            f"unknown genotype staging {requested!r}; expected one of {GENOTYPE_STAGINGS}"
        )
    if requested == "dense":
        return "dense"
    blockers = []
    if not getattr(source, "supports_packed", False):
        blockers.append(
            f"{type(source).__name__} has no native 2-bit layout"
        )
    if excluded_samples:
        blockers.append("relatedness exclusion subsets samples on host")
    if mesh is not None:
        blockers.append("sharding mesh stages the decoded layout")
    if not blockers:
        return "packed"
    if requested == "packed":
        raise ValueError(
            "genotype_staging='packed' unavailable: " + "; ".join(blockers)
        )
    return "dense"


@dataclass
class HostBatch:
    """Host-prepared batch: positional device args for the engine's step,
    plus any marker stats already known on the host (fused path) so sinks
    need not pull them back from the device."""

    batch: MarkerBatch
    device_args: tuple[np.ndarray, ...]
    host_maf: np.ndarray | None = None     # (m_batch,) observed MAF
    host_valid: np.ndarray | None = None   # (m_batch,) bool


class EngineDeviceState:
    """Everything an engine stages onto ONE device — an executor slot.

    The multi-device grid executor (DESIGN.md §12) gives every device its
    own slot: a compiled step, the H2D placement of each claimed batch's
    arrays, and whatever device caches the engine keeps (the lmm engine's
    per-scope rotation pair and per-(scope, block) rotated panels live in
    its subclass).  The serial executor is the degenerate single slot with
    ``device=None`` — placement then falls back to ``jnp.asarray`` on the
    implicit default device, the historical behavior bit for bit.

    Host-side amortized state (the residualized panel, GRM/REML results,
    rotated panels in float32) stays on the *engine* and is shared by every
    slot; only staged device arrays and the step's prolog memo are
    per-slot.  ``put`` is the one placement primitive: explicit
    ``jax.device_put`` onto the slot's device, so no slot ever leans on the
    process-global default device.
    """

    def __init__(self, engine: "ScanEngine", ctx: "EngineContext",
                 *, device: Any = None, step: Callable[..., dict] | None = None):
        self.engine = engine
        self.device = device
        if device is not None:
            # Steps close over context arrays (the covariate basis, the
            # multivariate whitening); a jitted computation whose constants
            # are committed to another device would be rejected — re-place
            # them on this slot's device before the step is built.  Bitwise
            # copies: placement moves bytes, never values.
            ctx = dataclasses.replace(
                ctx,
                q_basis=None if ctx.q_basis is None
                else jax.device_put(ctx.q_basis, device),
                whitening=None if ctx.whitening is None
                else jax.device_put(ctx.whitening, device),
            )
        self.ctx = ctx
        # A fresh step per slot: the one-slot prolog memo inside keys on the
        # staged array's identity, which is per-device — sharing a step
        # across slots would thrash the memo (and race it across worker
        # threads).  Same closure, same jaxpr, same compiled math.
        self.step = step if step is not None else engine.build_step(ctx)

    def put(self, arr: Any) -> jax.Array:
        """Stage one array onto this slot's device (async on accelerators)."""
        if self.device is None:
            return jnp.asarray(arr)
        return jax.device_put(arr, self.device)

    def stage(self, host_batch: "HostBatch") -> tuple:
        """Device-resident positional step args for one claimed batch."""
        return tuple(self.put(a) for a in host_batch.device_args)

    def panel_block(self, batch: MarkerBatch, block: TraitBlock) -> jax.Array:
        """Device panel slice for one grid cell (engines with
        ``uses_global_panel = False`` only; the driver's per-slot panel view
        serves global-panel engines)."""
        raise NotImplementedError(
            f"engine {self.engine.name!r} uses the driver's panel store"
        )

    def reset(self) -> None:
        """Drop per-slot pinned device state (the step memo's last batch)."""
        getattr(self.step, "reset", lambda: None)()


class ScanEngine:
    """Engine interface; subclasses register with ``@register_engine``.

    Every engine's step takes the cell's trait-block panel slice as its
    trailing argument.  ``uses_global_panel`` tells the driver who serves
    that slice: the driver's own residualized ``PanelStore`` (OLS engines),
    or the engine's device state's ``panel_block`` hook (the lmm engine,
    whose panels vary per LOCO scope as well as per block).  Device-staged
    state lives in per-executor-slot ``EngineDeviceState`` objects built by
    ``make_device_state`` — one per device, so a multi-device scan never
    shares staged arrays or prolog memos across devices.
    """

    name: str = "?"
    uses_global_panel: bool = True

    def validate(self, ctx: EngineContext) -> None:
        """Raise ValueError for unsupported (engine, context) combinations."""

    def setup_scan(
        self,
        source: Any,
        phenotypes: np.ndarray,
        covariates: np.ndarray | None,
        ctx: EngineContext,
    ) -> dict[str, Any] | None:
        """Optional amortized per-scan setup (after ``validate``, before
        ``build_step``).  May return overrides for the driver:
        ``{"dof": int, "info": dict}``.  Default: nothing to do."""
        return None

    def state_fingerprint(self) -> str | None:
        """Hashable summary of engine state a resume must match (e.g. the
        GRM spectrum); folded into the checkpoint fingerprint when set."""
        return None

    def build_step(self, ctx: EngineContext) -> Callable[..., dict[str, jax.Array]]:
        raise NotImplementedError

    def prepare_batch(self, source: Any, batch: MarkerBatch, ctx: EngineContext) -> HostBatch:
        raise NotImplementedError

    def make_device_state(
        self, ctx: EngineContext, *, device: Any = None,
        step: Callable[..., dict] | None = None,
    ) -> EngineDeviceState:
        """One executor slot's device residency; see ``EngineDeviceState``.
        ``step`` reuses an already-built step for the slot (the serial
        executor passes the plan's — keeping the shim's swappable ``_step``
        contract); by default the slot builds its own."""
        return EngineDeviceState(self, ctx, device=device, step=step)


_REGISTRY: dict[str, type[ScanEngine]] = {}


def register_engine(name: str) -> Callable[[type[ScanEngine]], type[ScanEngine]]:
    def deco(cls: type[ScanEngine]) -> type[ScanEngine]:
        cls.name = name
        _REGISTRY[name] = cls
        return cls

    return deco


def get_engine(name: str) -> ScanEngine:
    try:
        cls = _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown scan engine {name!r}; available: {available_engines()}"
        ) from None
    return cls()


def available_engines() -> list[str]:
    return sorted(_REGISTRY)


# --------------------------------------------------------------------- steps


def _dense_best_and_hits(nlp: jax.Array, t: jax.Array, hit_threshold: float) -> dict:
    """Reference-path summary outputs from a full masked nlp tile.

    The winner is the argmax over t^2 (first index on ties) with its nlp
    read from the tile — the same winner rule the sparse epilogue refines,
    so both paths agree bitwise even where the f32 nlp tile plateaus
    (distinct t^2 mapping to one nlp value) — the §13 monotonicity
    contract.
    """
    best_row = jnp.argmax(jnp.square(t), axis=0).astype(jnp.int32)
    return {
        "batch_best_nlp": jnp.take_along_axis(nlp, best_row[None, :], axis=0)[0],
        "batch_best_row": best_row,
        "batch_best_t": jnp.take_along_axis(t, best_row[None, :], axis=0)[0],
        "hit_count": jnp.sum(nlp >= hit_threshold).astype(jnp.int32),
    }


def _resolve_sparse(
    sparse_epilogue, mesh, options, hit_threshold, dof, hit_capacity,
    multivariate=False,
):
    """One gate for all three builders: the sparse epilogue needs a
    meaningful threshold (plan may refuse), an nlp-producing scan, no
    sharding mesh (the compaction gather is data-dependent — it does not
    shard; the multi-device grid executor, which jits per device, is the
    scaling path that does support it), and no multivariate omnibus (that
    screen consumes the full r tile in-step; keep its program identical to
    the audited dense one)."""
    if (
        not sparse_epilogue
        or mesh is not None
        or multivariate
        or not options.compute_neglog10p
    ):
        return None
    return plan_sparse_epilogue(hit_threshold, dof, capacity=hit_capacity)


def build_dense_step(
    *,
    n_samples: int,
    n_covariates: int,
    options: AssocOptions,
    mesh: Mesh | None = None,
    mode: str = "mp",
    hit_threshold: float = 7.301,
    maf_min: float = 0.0,
    q_basis: jax.Array | None = None,
    multivariate: bool = False,
    n_traits_eff: float = 1.0,
    whitening: jax.Array | None = None,
    trait_tile: int | None = None,
    split_prolog: bool = True,
    sparse_epilogue: bool = False,
    hit_capacity: int = 4096,
    packed_input: bool = False,
) -> Callable[..., dict[str, jax.Array]]:
    """Paper-faithful dense step: float dosages in, summary tiles out.

    ``packed_input`` accepts raw PLINK 2-bit bytes ``(M, ceil(N/4)) uint8``
    instead of float dosages and decodes them on device (DESIGN.md §17).
    The decode runs as its *own* jitted executable in front of the
    unchanged prolog/cell programs, so every downstream compiled artifact —
    and therefore every emitted bit — is identical to dense staging.
    ``trait_tile`` fixes the panel-axis GEMM tile (the scan passes its
    ``block_p``) so every trait-block decomposition computes identical
    tiles — the §10 bitwise contract.

    ``sparse_epilogue`` switches the p-value epilogue to the threshold-
    compacted sparse form (DESIGN.md §13): no full nlp tile; instead
    ``hit_idx``/``hit_r``/``hit_t`` compacted buffers of static
    ``hit_capacity`` plus ``screen_count`` (> capacity signals the host
    overflow fallback).  Hits, best-trait tables, and every persisted
    array are bitwise-identical to the dense path; mesh mode ignores the
    flag (the compaction gather does not shard).

    Like the lmm step, the computation splits into a once-per-marker-batch
    *prolog* (standardize + the exact-mode FWL residualization — everything
    trait-independent) and a per-cell *epilogue* (the panel GEMM + t/p).
    With ``split_prolog`` (the default) the prolog is jitted separately and
    memoized on the staged batch's array identity, so a blocked scan's
    inner trait-block loop pays the O(MN) standardization once per marker
    batch instead of once per grid cell (the ROADMAP "dense/fused prolog
    split" item).  ``split_prolog=False`` keeps the historical single-jit
    shape — same numbers bitwise (tests/test_screening.py asserts it): the
    cell GEMM consumes the identical float32 ``g_std`` either way, and
    standardization is elementwise/per-marker, so materializing it at the
    jit boundary cannot change a bit.
    """
    if packed_input and mesh is not None:
        raise ValueError("packed_input requires mesh=None (see resolve_genotype_staging)")
    if packed_input:
        from repro.kernels.gwas_dot import ops as kops

        decode = functools.partial(kops.decode_packed_device, n_samples=n_samples)
    dof = options.dof(n_samples, n_covariates)
    sparse = _resolve_sparse(
        sparse_epilogue, mesh, options, hit_threshold, dof, hit_capacity,
        multivariate=multivariate,
    )
    cell_options = (
        dataclasses.replace(options, sparse_epilogue=True) if sparse is not None
        else options
    )

    def prolog(g_raw: jax.Array):
        g_std, ms = standardize_genotype_batch(g_raw)
        if options.dof_mode == "exact":
            from repro.core.residualize import residualize_genotypes

            g_std = residualize_genotypes(g_std, q_basis)
        valid = ms.valid & (ms.maf >= maf_min) if maf_min > 0 else ms.valid
        return g_std, ms.maf, valid

    def cell(g_std, maf, valid, y_std) -> dict[str, jax.Array]:
        res = assoc_from_standardized(
            g_std, y_std, n_samples=n_samples, n_covariates=n_covariates,
            options=cell_options, trait_tile=trait_tile,
        )
        mask = valid[:, None]
        r = jnp.where(mask, res.r, 0.0)
        t = jnp.where(mask, res.t, 0.0)
        out = {"r": r, "t": t, "maf": maf, "valid": valid}
        if sparse is not None:
            out.update(sparse_epilogue_outputs(r, t, dof, sparse))
        else:
            nlp = jnp.where(mask, res.neglog10p, 0.0)
            out["nlp"] = nlp
            out.update(_dense_best_and_hits(nlp, t, hit_threshold))
        if multivariate:
            from repro.core import multivariate as mv

            omni, omni_nlp = mv.omnibus_chi2(
                out["r"], n_samples, n_traits_eff, whitening=whitening
            )
            out["omnibus"] = omni
            out["omnibus_nlp"] = omni_nlp
        return out

    def step_monolithic(g_raw: jax.Array, y_std: jax.Array) -> dict[str, jax.Array]:
        return cell(*prolog(g_raw), y_std)

    if mesh is None:
        if not split_prolog:
            mono_j = jax.jit(step_monolithic)
            if not packed_input:
                return mono_j
            # Decode-then-mono as two executables: the mono program is the
            # exact compiled artifact dense staging runs.
            return lambda g_raw, y_std: mono_j(decode(g_raw), y_std)
        prolog_j = jax.jit(prolog)
        cell_j = jax.jit(cell)
    else:
        sh = gwas_shardings(mesh, mode=mode)
        mv_spec = {"omnibus": sh["marker_vec"], "omnibus_nlp": sh["marker_vec"]} if multivariate else {}
        rep = NamedSharding(mesh, P())
        model_vec = NamedSharding(mesh, P("model"))
        out_shardings = {
            "r": sh["out"],
            "t": sh["out"],
            "nlp": sh["out"],
            "maf": sh["marker_vec"],
            "valid": sh["marker_vec"],
            "batch_best_nlp": model_vec,
            "batch_best_row": model_vec,
            "batch_best_t": model_vec,
            "hit_count": rep,
            **mv_spec,
        }
        if not split_prolog:
            return jax.jit(
                step_monolithic, in_shardings=(sh["g"], sh["y"]), out_shardings=out_shardings
            )
        prolog_j = jax.jit(
            prolog,
            in_shardings=(sh["g"],),
            out_shardings=(sh["g"], sh["marker_vec"], sh["marker_vec"]),
        )
        cell_j = jax.jit(
            cell,
            in_shardings=(sh["g"], sh["marker_vec"], sh["marker_vec"], sh["y"]),
            out_shardings=out_shardings,
        )

    # One-slot memo keyed on the staged genotype array's identity: the
    # driver passes the same device array for every trait block of a batch,
    # and a fresh one per batch.  Holding the reference pins the id.
    memo: dict[str, Any] = {"g": None, "out": None}

    def step(g_raw: jax.Array, y_std: jax.Array) -> dict[str, jax.Array]:
        if memo["g"] is not g_raw:
            # Packed staging: the device decode (its own executable) feeds
            # the identical prolog program — the decoded f32 never exists
            # on host and lives on device only for this batch's prolog.
            memo["out"] = prolog_j(decode(g_raw) if packed_input else g_raw)
            memo["g"] = g_raw
        return cell_j(*memo["out"], y_std)

    # The executor calls this at teardown so the last batch's staged raw +
    # standardized arrays don't stay pinned on device for the lifetime of a
    # cached plan.
    step.reset = lambda: memo.update(g=None, out=None)
    # The two jitted programs, for ahead-of-time lowering (compile checks).
    step.prolog, step.cell = prolog_j, cell_j
    return step


def build_fused_step(
    *,
    n_samples: int,
    n_covariates: int,
    options: AssocOptions,
    mesh: Mesh | None = None,
    hit_threshold: float = 7.301,
    block_m: int = 256,
    block_n: int = 512,
    block_p: int = 256,
    interpret: bool | None = None,
    input_dtype: str | None = None,
    sparse_epilogue: bool = False,
    hit_capacity: int = 4096,
    packed_input: bool = False,
) -> Callable[..., dict[str, jax.Array]]:
    """Beyond-paper fused step: 2-bit packed slabs in (kernel layout),
    summary tiles out.  'mp' sharding only — the in-kernel epilogue requires
    complete sample contractions per device (DESIGN.md §5).

    ``input_dtype`` selects the kernel's GEMM input dtype ("fp32" | "bf16");
    the in-kernel accumulation and the epilogue (t, -log10 p, argmax) stay
    float32 either way — the GEMM-bf16 / epilogue-fp32 split audited by the
    oracle suite.  ``None`` defers to ``options.precision`` (the historical
    plumbing).  ``sparse_epilogue`` — see ``build_dense_step``; the kernel
    still emits the full r/t tiles, only the p-value work is compacted.

    ``packed_input`` takes raw PLINK bytes ``(M, ceil(N/4))`` instead of the
    kernel's tile-local layout and performs the tile repack *on device* as
    its own jitted byte shuffle (DESIGN.md §17) — killing the host
    ``unpack_plink_to_codes`` + ``pack_tiled`` round trip, so host prep is
    a memcpy plus the LUT marker-stat pass.  The kernel step itself is the
    unchanged compiled program; output bits are identical."""
    from repro.kernels.gwas_dot.gwas_dot import build_gwas_dot

    if packed_input and mesh is not None:
        raise ValueError("packed_input requires mesh=None (see resolve_genotype_staging)")
    if interpret is None:
        interpret = pallas_interpret()
    dof = options.dof(n_samples, n_covariates)
    sparse = _resolve_sparse(
        sparse_epilogue, mesh, options, hit_threshold, dof, hit_capacity
    )
    use_bf16 = input_dtype == "bf16" or (input_dtype is None and options.precision == "bf16")
    input_dtype = jnp.bfloat16 if use_bf16 else jnp.float32

    def kernel_local(packed, mean2d, inv2d, y):
        m_loc = packed.shape[0]
        n_pad = packed.shape[1] * 4
        p_loc = y.shape[1]
        call = build_gwas_dot(
            m_loc, n_pad, p_loc,
            block_m=block_m, block_n=block_n, block_p=block_p,
            n_samples=n_samples, dof=dof,
            input_dtype=input_dtype, interpret=interpret,
        )
        return tuple(call(packed, mean2d, inv2d, y))

    if mesh is not None:
        dp = batch_axes(mesh)
        kernel_fn = jax.shard_map(
            kernel_local,
            mesh=mesh,
            in_specs=(P(dp, None), P(dp, None), P(dp, None), P(None, "model")),
            out_specs=(P(dp, "model"), P(dp, "model")),
            # pallas_call out_shapes carry no vma metadata; the kernel is
            # elementwise-independent per shard so the check is vacuous here.
            check_vma=False,
        )
    else:
        kernel_fn = kernel_local

    def step(packed, mean2d, inv2d, valid, y_std):
        p_true = y_std.shape[1]
        pad_p = (-p_true) % block_p
        pad_n = packed.shape[1] * 4 - y_std.shape[0]  # packed samples are tile-padded
        if pad_p or pad_n:
            y_std = jnp.pad(y_std, ((0, pad_n), (0, pad_p)))
        r, t = kernel_fn(packed, mean2d, inv2d, y_std)
        if pad_p:
            r = r[:, :p_true]
            t = t[:, :p_true]
        mask = valid[:, None]
        r = jnp.where(mask, r, 0.0)
        t = jnp.where(mask, t, 0.0)
        out = {"r": r, "t": t}
        if sparse is not None:
            out.update(sparse_epilogue_outputs(r, t, dof, sparse))
        else:
            nlp = jnp.where(mask, _stats.neglog10_p_from_t(t, dof), 0.0)
            out["nlp"] = nlp
            out.update(_dense_best_and_hits(nlp, t, hit_threshold))
        return out

    if mesh is None:
        step_j = jax.jit(step)
        if not packed_input:
            return step_j
        from repro.kernels.gwas_dot import ops as kops

        # One-slot memo like the dense/lmm prologs: the device repack runs
        # once per staged batch, then every trait-block cell reuses the
        # tiled bytes through the unchanged kernel step.
        memo: dict[str, Any] = {"g": None, "tiled": None}

        def step_packed(plink_packed, mean2d, inv2d, valid, y_std):
            if memo["g"] is not plink_packed:
                memo["tiled"] = kops.repack_plink_tiled_device(
                    plink_packed,
                    n_samples=n_samples,
                    block_n=block_n,
                    block_m=block_m,
                )
                memo["g"] = plink_packed
            return step_j(memo["tiled"], mean2d, inv2d, valid, y_std)

        step_packed.reset = lambda: memo.update(g=None, tiled=None)
        return step_packed
    sh = gwas_shardings(mesh, mode="mp")
    model_vec = NamedSharding(mesh, P("model"))
    return jax.jit(
        step,
        in_shardings=(sh["packed"], sh["packed"], sh["packed"], sh["marker_vec"], sh["y"]),
        out_shardings={
            "r": sh["out"],
            "t": sh["out"],
            "nlp": sh["out"],
            "batch_best_nlp": model_vec,
            "batch_best_row": model_vec,
            "batch_best_t": model_vec,
            "hit_count": NamedSharding(mesh, P()),
        },
    )


def build_lmm_step(
    *,
    n_samples: int,
    n_covariates: int,
    options: AssocOptions,
    mesh: Mesh | None = None,
    hit_threshold: float = 7.301,
    maf_min: float = 0.0,
    epilogue: str = "dense",
    block_m: int = 256,
    block_p: int = 256,
    sparse_epilogue: bool = False,
    hit_capacity: int = 4096,
    packed_input: bool = False,
) -> Callable[..., dict[str, jax.Array]]:
    """Mixed-model step: standardize -> rotate into the (whitened) GRM
    eigenbasis -> project out the whitened design -> the unchanged
    correlation epilogue (DESIGN.md §9).

    Signature: ``step(g_raw, rotation, qhat, y_std)`` — the rotation matrix
    and panel ride in ``device_args`` because they vary per LOCO scope.
    The GLS dof is structurally ``N - 2 - q`` (the whitened design counts
    its intercept), so the epilogue always runs in exact-dof mode.

    ``epilogue="dense"`` computes t/p in plain XLA; ``"fused"`` routes
    Eq. 3 through the standalone Pallas t-statistic kernel
    (``kernels.tstat``) — identical numbers, exercised by the oracle suite.

    ``block_p`` doubles as the panel-axis GEMM tile (``trait_tile`` of
    ``core.association.correlation``) so blocked and unblocked scans
    compute identical tiles (§10).

    Internally the step is a once-per-marker-batch *prolog* (standardize,
    rotation GEMM, whitened-design projection — everything trait-
    independent, including the dominant (M,N)x(N,N) GEMM) plus a per-cell
    *epilogue* (the panel GEMM + t/p).  The prolog result is memoized on
    the staged batch's array identity, so a blocked scan's inner trait-
    block loop pays the genotype-side work once per marker batch, not once
    per grid cell.  The public signature is unchanged.

    ``sparse_epilogue`` — see ``build_dense_step``.  With
    ``epilogue="fused"`` the t^2 screen additionally fuses into the Pallas
    t-statistic pass (``kernels.tstat.screen_compact``): Eq. 3, the screen
    compare, and the per-block survivor counts run in one kernel; the exact
    CF then touches only the compacted lanes.
    """
    if epilogue not in ("dense", "fused"):
        raise ValueError(f"unknown lmm epilogue {epilogue!r}")
    if packed_input and mesh is not None:
        raise ValueError("packed_input requires mesh=None (see resolve_genotype_staging)")
    if packed_input:
        from repro.kernels.gwas_dot import ops as kops

        decode = functools.partial(kops.decode_packed_device, n_samples=n_samples)
    opts = dataclasses.replace(options, dof_mode="exact")
    dof = opts.dof(n_samples, n_covariates)
    sparse = _resolve_sparse(
        sparse_epilogue, mesh, opts, hit_threshold, dof, hit_capacity
    )

    from repro.core.association import correlation
    from repro.core.residualize import residualize_genotypes

    def prolog(g_raw, rotation, qhat):
        g_std, ms = standardize_genotype_batch(g_raw)
        g_rot = jax.lax.dot_general(
            g_std, rotation, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
        g_fin = residualize_genotypes(g_rot, qhat)
        valid = ms.valid & (ms.maf >= maf_min) if maf_min > 0 else ms.valid
        return g_fin, ms.maf, valid

    cell_opts = (
        dataclasses.replace(opts, sparse_epilogue=True) if sparse is not None
        else opts
    )

    def cell(g_fin, maf, valid, y_std):
        mask = valid[:, None]
        screen = None
        nlp = None
        if epilogue == "fused":
            r = jnp.clip(
                correlation(g_fin, y_std, n_samples, precision=opts.precision,
                            trait_tile=block_p),
                -1.0, 1.0,
            )
            # Mask before the kernel: invalid lanes map to r=0 -> t=0
            # exactly, so masked tiles are identical either way and the
            # fused screen can never admit a masked lane.
            r = jnp.where(mask, r, 0.0)
            if sparse is not None:
                from repro.kernels.tstat import screen_compact

                t, idx, screen_count = screen_compact(
                    r, dof, sparse.t2_screen, sparse.capacity,
                    block_m=block_m, block_p=block_p,
                )
                screen = (idx, screen_count)
            else:
                from repro.kernels.tstat import tstat

                t = tstat(r, dof, block_m=block_m, block_p=block_p)
                nlp = jnp.where(mask, _stats.neglog10_p_from_t(t, dof), 0.0)
        else:
            res = assoc_from_standardized(
                g_fin, y_std, n_samples=n_samples, n_covariates=n_covariates,
                options=cell_opts, trait_tile=block_p,
            )
            r = jnp.where(mask, res.r, 0.0)
            t = jnp.where(mask, res.t, 0.0)
            if sparse is None:
                nlp = jnp.where(mask, res.neglog10p, 0.0)
        out = {"r": r, "t": t, "maf": maf, "valid": valid}
        if sparse is not None:
            out.update(sparse_epilogue_outputs(r, t, dof, sparse, screen=screen))
        else:
            out["nlp"] = nlp
            out.update(_dense_best_and_hits(nlp, t, hit_threshold))
        return out

    if mesh is None:
        prolog_j = jax.jit(prolog)
        cell_j = jax.jit(cell)
    else:
        sh = gwas_shardings(mesh, mode="mp")
        rep = NamedSharding(mesh, P())
        model_vec = NamedSharding(mesh, P("model"))
        prolog_j = jax.jit(
            prolog,
            in_shardings=(sh["g"], rep, rep),
            out_shardings=(sh["g"], sh["marker_vec"], sh["marker_vec"]),
        )
        cell_j = jax.jit(
            cell,
            in_shardings=(sh["g"], sh["marker_vec"], sh["marker_vec"], sh["y"]),
            out_shardings={
                "r": sh["out"],
                "t": sh["out"],
                "nlp": sh["out"],
                "maf": sh["marker_vec"],
                "valid": sh["marker_vec"],
                "batch_best_nlp": model_vec,
                "batch_best_row": model_vec,
                "batch_best_t": model_vec,
                "hit_count": rep,
            },
        )

    # One-slot memo keyed on the staged genotype array's identity: the
    # driver passes the same device array for every trait block of a batch,
    # and a fresh one per batch.  Holding the reference pins the id.
    memo: dict[str, Any] = {"g": None, "out": None}

    def step(g_raw, rotation, qhat, y_std):
        if memo["g"] is not g_raw:
            # See build_dense_step: under packed staging the device decode
            # is its own executable in front of the unchanged prolog.
            g_in = decode(g_raw) if packed_input else g_raw
            memo["out"] = prolog_j(g_in, rotation, qhat)
            memo["g"] = g_raw
        return cell_j(*memo["out"], y_std)

    # See build_dense_step: drop the pinned last batch at executor teardown.
    step.reset = lambda: memo.update(g=None, out=None)
    step.prolog, step.cell = prolog_j, cell_j
    return step


# ------------------------------------------------------------------- engines


@register_engine("dense")
class DenseEngine(ScanEngine):
    """XLA GEMM over float dosages — the paper-faithful reference engine.
    Supports both 'mp' and 'sample' sharding and the multivariate screen."""

    def build_step(self, ctx: EngineContext) -> Callable[..., dict[str, jax.Array]]:
        return build_dense_step(
            n_samples=ctx.n_samples,
            n_covariates=ctx.n_covariates,
            options=ctx.options,
            mesh=ctx.mesh,
            mode=ctx.mode,
            hit_threshold=ctx.hit_threshold,
            maf_min=ctx.maf_min,
            q_basis=ctx.q_basis,
            multivariate=ctx.multivariate,
            n_traits_eff=ctx.n_traits_eff,
            whitening=ctx.whitening,
            trait_tile=ctx.block_p,
            sparse_epilogue=ctx.sparse_epilogue,
            hit_capacity=ctx.hit_capacity,
            packed_input=ctx.genotype_staging == "packed",
        )

    def prepare_batch(self, source: Any, batch: MarkerBatch, ctx: EngineContext) -> HostBatch:
        if ctx.genotype_staging == "packed":
            # Stage ceil(N/4) bytes/marker through the shared slab cache;
            # the step's device decode front-end expands them (§17).
            from repro.io.packed_cache import read_packed_cached

            return HostBatch(batch, (read_packed_cached(source, batch.lo, batch.hi),))
        dosages = source.read_dosages(batch.lo, batch.hi)
        if ctx.excluded_samples:
            dosages = dosages[:, ctx.keep]
        return HostBatch(batch, (np.asarray(dosages, np.float32),))


@register_engine("fused")
class FusedEngine(ScanEngine):
    """2-bit Pallas engine: packed slabs stay packed until the kernel's
    inner loop; marker stats come from the host repack pass, so the device
    sees N/4 bytes per marker."""

    def validate(self, ctx: EngineContext) -> None:
        if ctx.mode != "mp":
            raise ValueError("fused engine supports marker x phenotype sharding only")

    def build_step(self, ctx: EngineContext) -> Callable[..., dict[str, jax.Array]]:
        return build_fused_step(
            n_samples=ctx.n_samples,
            n_covariates=ctx.n_covariates,
            options=ctx.options,
            mesh=ctx.mesh,
            hit_threshold=ctx.hit_threshold,
            block_m=ctx.block_m,
            block_n=ctx.block_n,
            block_p=ctx.block_p,
            # "bf16" forces the kernel's low-precision GEMM; the default
            # defers to options.precision (the historical plumbing).
            input_dtype="bf16" if ctx.input_dtype == "bf16" else None,
            sparse_epilogue=ctx.sparse_epilogue,
            hit_capacity=ctx.hit_capacity,
            packed_input=ctx.genotype_staging == "packed",
        )

    def prepare_batch(self, source: Any, batch: MarkerBatch, ctx: EngineContext) -> HostBatch:
        from repro.kernels.gwas_dot import ops as kops

        m_batch = batch.n_markers
        if ctx.genotype_staging == "packed":
            # Host prep at memcpy cost: cached raw slab + LUT marker stats.
            # The unpack/re-pack byte shuffle moved onto the device (§17);
            # stat vectors still pad to the block_m geometry the kernel
            # step expects (the device repack pads its rows to match).
            from repro.io.packed_cache import read_packed_cached

            plink_packed = read_packed_cached(source, batch.lo, batch.hi)
            mean, inv_std, valid = kops.marker_stats_from_packed(
                plink_packed, ctx.n_samples
            )
            if ctx.maf_min > 0:
                af = mean / 2.0
                maf = np.minimum(af, 1.0 - af)
                valid &= maf >= ctx.maf_min
                inv_std = np.where(valid, inv_std, 0.0).astype(np.float32)
            pad_m = (-m_batch) % ctx.block_m
            if pad_m:
                mean = np.pad(mean, (0, pad_m))
                inv_std = np.pad(inv_std, (0, pad_m))
                valid = np.pad(valid, (0, pad_m))
            maf = np.minimum(mean / 2.0, 1.0 - mean / 2.0)
            return HostBatch(
                batch,
                (plink_packed, mean.reshape(-1, 1), inv_std.reshape(-1, 1), valid),
                host_maf=maf[:m_batch],
                host_valid=valid[:m_batch],
            )
        n_total = len(ctx.keep) if ctx.keep is not None else ctx.n_samples
        plink_packed = source.read_packed(batch.lo, batch.hi)
        codes = kops.unpack_plink_to_codes(plink_packed, n_total)
        if ctx.excluded_samples:
            codes = codes[:, ctx.keep]
        mean, inv_std, valid = kops.marker_stats_from_codes(codes)
        if ctx.maf_min > 0:
            af = mean / 2.0
            maf = np.minimum(af, 1.0 - af)
            valid &= maf >= ctx.maf_min
            inv_std = np.where(valid, inv_std, 0.0).astype(np.float32)
        packed = kops.pack_tiled(codes, ctx.block_n)
        pad_m = (-packed.shape[0]) % ctx.block_m
        if pad_m:
            packed = np.pad(packed, ((0, pad_m), (0, 0)), constant_values=0b01)
            mean = np.pad(mean, (0, pad_m))
            inv_std = np.pad(inv_std, (0, pad_m))
            valid = np.pad(valid, (0, pad_m))
        maf = np.minimum(mean / 2.0, 1.0 - mean / 2.0)
        return HostBatch(
            batch,
            (packed, mean.reshape(-1, 1), inv_std.reshape(-1, 1), valid),
            host_maf=maf[:m_batch],
            host_valid=valid[:m_batch],
        )


class _LMMDeviceState(EngineDeviceState):
    """One device's share of the lmm engine: the staged per-scope
    (rotation, qhat) pair and the per-(scope, trait-block) rotated panel
    slices, each LRU-bounded *per slot*.  The host float32 panels live on
    the engine (shared across slots); every slot stages its own copies with
    explicit placement, so a multi-device LOCO scan holds at most
    ``_DEV_SCOPES_MAX`` rotations per device, never one shared set on the
    default device."""

    def __init__(self, engine: "LMMEngine", ctx: EngineContext,
                 *, device: Any = None, step: Callable[..., dict] | None = None):
        super().__init__(engine, ctx, device=device, step=step)
        # scope -> staged (rotation, qhat); evicting a scope drops its
        # resident panel blocks with it
        self._dev = DeviceLRU(
            engine._DEV_SCOPES_MAX,
            lambda sid: (
                self.put(engine._scopes[sid].rotation),
                self.put(engine._scopes[sid].qhat),
            ),
            on_evict=lambda sid: self._dev_y.drop_if(lambda k: k[0] == sid),
        )
        # (scope, block) -> staged panel slice
        self._dev_y = DeviceLRU(
            max(1, ctx.panel_resident_blocks), self._load_panel_block
        )

    def _load_panel_block(self, key: tuple[int, int]) -> jax.Array:
        sid, block_index = key
        blk = self.engine._trait_blocks[block_index]
        return self.put(self.engine._scopes[sid].y_block(blk.lo, blk.hi))

    def stage(self, host_batch: HostBatch) -> tuple:
        """(dosages, rotation, qhat) on this slot's device: the dosage copy
        is fresh per batch, the scope pair comes from the slot's LRU —
        staged once and shared by every batch of that scope on this
        device."""
        sid = host_batch.batch.source_id if self.engine._loco else -1
        rotation, qhat = self._dev.get(sid)
        return (self.put(host_batch.device_args[0]), rotation, qhat)

    def panel_block(self, batch: MarkerBatch, block: TraitBlock) -> jax.Array:
        """Rotated-panel slice for one grid cell, LRU-cached on this slot's
        device so a panel that fits stays resident while a paper-scale one
        streams block-by-block.  The slice comes from the scope's host
        float32 panel, which keeps the blocked scan bitwise-identical to
        the unblocked one — the float64 whitening ran panel-wide at setup
        (the global REML fit materializes the rotated panel anyway,
        DESIGN.md §10)."""
        sid = batch.source_id if self.engine._loco else -1
        return self._dev_y.get((sid, block.index))

    def reset(self) -> None:
        """Slot teardown: the step memo (base) plus this slot's staged
        rotation pairs and rotated panel blocks — a closed multi-device
        scan must pin nothing on its devices.  The shared host-side
        float32 panels on the engine are untouched (amortized state)."""
        super().reset()
        if self.device is not None:
            self._dev_y.clear()
            self._dev.clear()


@register_engine("lmm")
class LMMEngine(ScanEngine):
    """Linear mixed model: streamed GRM + one-time rotation (core.grm,
    core.lmm).  ``setup_scan`` amortizes the expensive work — GRM pass,
    eigendecomposition, REML — once per scan (per LOCO chromosome);
    ``prepare_batch`` then only reads dosages, so the per-batch device cost
    is one extra (M, N) x (N, N) GEMM on top of the OLS scan.  All device
    staging — the scope's rotation/basis pair and the per-(scope,
    trait-block) rotated panel slices — lives in ``_LMMDeviceState``, one
    per executor slot (``uses_global_panel = False``), LRU-bounded per
    device."""

    uses_global_panel = False

    # Scopes arrive shard-sequentially (the planner never interleaves
    # shards), but the prefetch window may straddle one boundary — so two
    # resident scopes bound device memory at ~2 (N,N) rotations, not one
    # per chromosome.
    _DEV_SCOPES_MAX = 2

    def __init__(self) -> None:
        self._scopes: dict[int, Any] = {}       # scope -> core.lmm.RotatedPanel
        self._trait_blocks: tuple[TraitBlock, ...] = ()
        self._loco = False
        self._fingerprint: str | None = None
        self._dof: int | None = None
        self._n_cov: int | None = None

    def validate(self, ctx: EngineContext) -> None:
        if ctx.mode != "mp":
            raise ValueError("lmm engine supports marker x phenotype sharding only")
        if ctx.multivariate:
            raise ValueError("lmm engine and the multivariate screen are exclusive")
        if ctx.lmm_epilogue not in ("dense", "fused"):
            raise ValueError(f"unknown lmm epilogue {ctx.lmm_epilogue!r}")

    def setup_scan(self, source, phenotypes, covariates, ctx: EngineContext):
        from repro.core.grm import grm_spectrum, spectrum_fingerprint, stream_grm
        from repro.core.lmm import rotate_panel

        self._trait_blocks = ctx.trait_blocks
        grm = stream_grm(
            source,
            keep=ctx.keep if ctx.excluded_samples else None,
            batch_markers=ctx.grm_batch_markers,
            method=ctx.grm_method,
            maf_min=ctx.maf_min,
            io_workers=ctx.io_workers,
            # Same currency as the scan: packed batches flow through the
            # shared slab cache + device decode, so GRM and scan share one
            # read per batch (satellite of §17).
            staging=ctx.genotype_staging,
        )
        if ctx.loco and grm.n_shards < 2:
            raise ValueError(
                "loco=True needs a per-chromosome fileset (>= 2 genotype shards)"
            )
        scopes = list(range(grm.n_shards)) if ctx.loco else [-1]
        spectra: dict[int, np.ndarray] = {}
        for sid in scopes:
            k = grm.loco(sid) if ctx.loco else grm.full()
            s, u = grm_spectrum(k)
            spectra[sid] = s
            self._scopes[sid] = rotate_panel(
                phenotypes, covariates, s, u, delta=ctx.lmm_delta
            )
        self._loco = ctx.loco
        first = next(iter(self._scopes.values()))
        self._dof = first.dof
        self._n_cov = first.n_covariates
        deltas = {sid: p.delta for sid, p in self._scopes.items()}
        # Deltas enter the fingerprint rounded to the same significant-digit
        # budget as the spectrum hash, so a resume on a different BLAS build
        # (last-bit REML jitter) is not spuriously refused.
        delta_sig = [(sid, f"{d:.6g}") for sid, d in sorted(deltas.items())]
        self._fingerprint = f"{spectrum_fingerprint(spectra)}:{delta_sig}"
        info: dict[str, Any] = {
            "grm_method": grm.method,
            "scopes": len(scopes),
            "loco": ctx.loco,
            "delta": deltas if ctx.loco else first.delta,
            "spectrum_hash": spectrum_fingerprint(spectra),
        }
        if first.reml is not None:
            info["h2"] = first.reml.h2
            info["delta_per_trait"] = first.reml.delta
        return {"dof": self._dof, "info": info}

    def state_fingerprint(self) -> str | None:
        return self._fingerprint

    def build_step(self, ctx: EngineContext) -> Callable[..., dict[str, jax.Array]]:
        if self._dof is None:
            raise RuntimeError("setup_scan must run before build_step")
        return build_lmm_step(
            n_samples=ctx.n_samples,
            n_covariates=self._n_cov,
            options=ctx.options,
            mesh=ctx.mesh,
            hit_threshold=ctx.hit_threshold,
            maf_min=ctx.maf_min,
            epilogue=ctx.lmm_epilogue,
            block_m=ctx.block_m,
            block_p=ctx.block_p,
            sparse_epilogue=ctx.sparse_epilogue,
            hit_capacity=ctx.hit_capacity,
            packed_input=ctx.genotype_staging == "packed",
        )

    def make_device_state(
        self, ctx: EngineContext, *, device: Any = None,
        step: Callable[..., dict] | None = None,
    ) -> EngineDeviceState:
        return _LMMDeviceState(self, ctx, device=device, step=step)

    def prepare_batch(self, source: Any, batch: MarkerBatch, ctx: EngineContext) -> HostBatch:
        """Host side only: read and subset dosages.  The scope's rotation
        pair is attached at staging time by the slot's device state (it is
        device-resident state, not host batch payload)."""
        if ctx.genotype_staging == "packed":
            from repro.io.packed_cache import read_packed_cached

            return HostBatch(batch, (read_packed_cached(source, batch.lo, batch.hi),))
        dosages = source.read_dosages(batch.lo, batch.hi)
        if ctx.excluded_samples:
            dosages = dosages[:, ctx.keep]
        return HostBatch(batch, (np.asarray(dosages, np.float32),))
