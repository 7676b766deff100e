#!/usr/bin/env python3
"""Smoke run of the GWAS scan on a TPU, through the CLI entry point.

    python chip_smoke.py [--seed 0]          # one chip
    python chip_smoke.py --four-chips        # the multi-device executor only

One chip, one process.  Phases, in order; each raises on failure, so the
script then exits non-zero without its final line:

  cohort  a synthetic cohort at the paper's width (``configs/gwas_ukb.py``):
          N=23,000 samples, 12 covariates, P=2,048 traits and M=16,384
          markers (two 8,192-marker batches; M is the only cut), with planted
          effects, written as PLINK .bed plus phenotype/covariate tables
  fused   ``repro.launch.gwas scan --engine fused`` (auto staging)
  dense   the same scan with ``--engine dense``
  check   planted effects in both hits.tsv, lambda_gc and best-p
          calibration, a float64 OLS reference over 64 traits, the fused
          kernel's r against float64, fused-vs-dense hit sets, and
          ``tpu_custom_call`` in the fused step's compiled HLO
  lmm     ``--engine lmm --lmm-epilogue fused`` at N=4,096, P=256 (N is cut
          because the GRM eigendecomposition runs on the host in float64 at
          O(N^3)), and the screen kernel against numpy on the chip

``--four-chips`` runs only this: the fused scan of the same cohort with
``--devices 4`` and with ``--devices 1`` in one process.  Their TSVs must be
byte-identical and every executor worker must have claimed cells.

The last line of stdout is ``{"ok": true, "device": {...}}``.  Every time
printed is a smoke number (one cold run, compile included where said), not
a benchmark metric.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys
import tempfile
import time
from dataclasses import dataclass

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


@dataclass(frozen=True)
class Sizes:
    n_samples: int = 23_000
    n_covariates: int = 12
    n_traits: int = 2_048
    n_markers: int = 16_384
    batch_markers: int = 8_192
    n_causal: int = 8
    effect_size: float = 0.1
    ref_traits: int = 64           # traits the float64 reference covers
    kernel_markers: int = 1_024    # slice for the direct kernel-vs-float64 check
    lmm_samples: int = 4_096
    lmm_traits: int = 256
    lmm_effect_size: float = 0.15


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (any thread)."""

    def __init__(self) -> None:
        import jax

        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event in _COMPILE_EVENTS:
            self.total += duration


class Phases:
    def __init__(self) -> None:
        self.clock = CompileClock()

    @contextlib.contextmanager
    def __call__(self, name: str):
        c0, t0 = self.clock.total, time.perf_counter()
        yield
        wall = time.perf_counter() - t0
        comp = self.clock.total - c0
        print(f"[{name}] wall {wall:.2f} s; JAX trace+compile {comp:.2f} s "
              "(summed over threads)", flush=True)


# ------------------------------------------------------------------ cohort


def write_cohort(sizes: Sizes, seed: int, stem: str, *, n_samples: int,
                 n_traits: int, effect_size: float):
    from repro.io import synth

    cohort = synth.make_cohort(
        n_samples=n_samples, n_markers=sizes.n_markers, n_traits=n_traits,
        n_covariates=sizes.n_covariates, n_causal=sizes.n_causal,
        effect_size=effect_size, seed=seed,
    )
    return cohort, synth.write_cohort_files(cohort, stem, bgen=False)


def scan(paths: dict, out: str, *flags: str) -> dict:
    """One ``repro.launch.gwas scan`` in this process; returns summary.json."""
    from repro.launch import gwas

    argv = ["scan", "--genotypes", paths["bed"], "--pheno", paths["pheno"],
            "--covar", paths["cov"], "--out", out, *flags]
    with contextlib.redirect_stdout(io.StringIO()):
        gwas.main(argv)
    with open(os.path.join(out, "summary.json")) as f:
        summary = json.load(f)
    print(f"  {' '.join(flags)}: staging={summary['genotype_staging']} "
          f"hits={summary['hits']} lambda_gc={summary['lambda_gc']:.4f} "
          f"scan_wall={summary['wall_s']:.2f} s "
          f"markers/s={summary['markers_per_s']:.0f} (cold)", flush=True)
    return summary


def read_hits(out: str) -> dict:
    hits = {}
    with open(os.path.join(out, "hits.tsv")) as f:
        next(f)
        for line in f:
            m, t, r, tt, nlp = line.split("\t")
            hits[(int(m[2:]), int(t[5:]))] = (float(r), float(tt), float(nlp))
    return hits


def read_best(out: str) -> tuple[np.ndarray, np.ndarray]:
    marker, nlp = [], []
    with open(os.path.join(out, "per_trait_best.tsv")) as f:
        next(f)
        for line in f:
            _, m, v = line.split("\t")
            marker.append(int(m[2:]))
            nlp.append(float(v))
    return np.asarray(marker), np.asarray(nlp)


# --------------------------------------------------------------- reference

# Tolerances of the float64 comparison, and why.  The scan runs in float32:
# the standardized inputs carry relative rounding near 6e-8 and the GEMM
# accumulates 45 blocked partial sums of 512 samples, which leaves |dr| near
# 1e-7; R_TOL allows ten times that.  A single-pass bf16 product (8-bit
# mantissa) leaves |dr| near 1e-5 at this N, so R_TOL separates the two.
# Statistics are compared in the units the TSVs print: r propagated through
# Eq. 3 and the t tail (local slopes from the float64 reference), plus half
# a unit in the last printed decimal (t: 4 decimals, -log10 p: 3).  On top
# of that comes the error of the f32 -log10 p of an exact t, the canonical
# refine (core/stats.neglog10_p_from_t), which is a function of t alone:
# 7.4e-5 relative on XLA:CPU at this dof, and on a v5e chip up to 1.82e-4
# relative against scipy over t in [0.5, 25], because the chip's f32 log1p
# is good to ~1.5e-4 relative and the tail lane scales it by dof/2.  That
# is the refine's arithmetic, not the dot: a bf16-sized |dr| of 1e-5 would
# move -log10 p near t=16 by ~1e-2, which only the R_TOL term admits.
R_TOL = 1e-6
T_PRINT = 5e-5
NLP_PRINT = 5e-4
NLP_F32_REL = 2.5e-4
# The residualized panel (unit-variance columns): the f32 QR of the
# 13-column basis and two f32 projections leave max |dy| near 2e-5 at
# N=23,000 (2.17e-5 on XLA:CPU); a bf16 pass leaves ~1e-3.
PANEL_TOL = 1e-4


def _nlp64(t: np.ndarray, dof: float) -> np.ndarray:
    from scipy import stats as sps

    return -(sps.t.logsf(np.abs(t), dof) + np.log(2.0)) / np.log(10.0)


class OlsReference:
    """Paper-mode association statistics in float64 (Eq. 1-3): covariates
    projected out of the phenotypes, standardized mean-imputed genotypes,
    dof = N - 2.  Phenotypes and covariates are rounded exactly as the
    written tables carry them."""

    def __init__(self, cohort, traits: int):
        n = cohort.dosages.shape[1]
        as_written = np.vectorize(lambda v: float(f"{v:.6g}"))
        self.y_raw = y = as_written(cohort.phenotypes[:, :traits].astype(np.float64))
        self.c_raw = c = as_written(cohort.covariates.astype(np.float64))
        c = (c - c.mean(0)) / c.std(0)
        q, _ = np.linalg.qr(np.concatenate([np.ones((n, 1)), c], axis=1))
        y = y - q @ (q.T @ y)
        self.y = y / np.sqrt(np.mean(y * y, axis=0))
        self.n = n
        self.dof = float(n - 2)
        self.dosages = cohort.dosages

    def g_std(self, lo: int, hi: int) -> np.ndarray:
        d = self.dosages[lo:hi].astype(np.float64)
        miss = d < 0
        mean = np.where(miss, 0.0, d).sum(1) / np.maximum((~miss).sum(1), 1)
        d = np.where(miss, mean[:, None], d) - mean[:, None]
        return d / np.sqrt(np.mean(d * d, axis=1, keepdims=True))

    def r(self, chunk: int = 1024) -> np.ndarray:
        m = self.dosages.shape[0]
        return np.concatenate(
            [self.g_std(lo, min(lo + chunk, m)) @ self.y / self.n
             for lo in range(0, m, chunk)]
        )

    def t(self, r: np.ndarray) -> np.ndarray:
        return r * np.sqrt(self.dof / (1.0 - r * r))

    def tolerances(self, r: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """(t, -log10 p) tolerances at these r values."""
        h = 1e-7
        t0, t1 = self.t(r), self.t(r + h)
        dt = np.abs(t1 - t0) / h
        nlp = _nlp64(t0, self.dof)
        dnlp = np.abs(_nlp64(t1, self.dof) - nlp) / h
        return T_PRINT + R_TOL * dt, NLP_PRINT + R_TOL * dnlp + NLP_F32_REL * nlp


def check_against_reference(name: str, out: str, ref: OlsReference,
                            r_ref: np.ndarray) -> dict:
    """The engine's per-trait best and its hits against float64."""
    k = r_ref.shape[1]
    t_ref = ref.t(r_ref)
    best_m, best_nlp = read_best(out)
    best_m, best_nlp = best_m[:k], best_nlp[:k]
    cols = np.arange(k)
    top = np.argmax(np.abs(r_ref), axis=0)
    # The engine's winner may differ from float64's only where the two tie
    # at the tolerance's resolution: within 2 * R_TOL in |r|.
    shortfall = np.abs(r_ref[top, cols]) - np.abs(r_ref[best_m, cols])
    wrong = shortfall > 2 * R_TOL
    check(not wrong.any(), f"{name}: best marker differs from float64: " + "; ".join(
        f"trait{j} engine rs{best_m[j]:08d} |r|={abs(r_ref[best_m[j], j]):.7f} "
        f"float64 rs{top[j]:08d} |r|={abs(r_ref[top[j], j]):.7f}"
        for j in np.flatnonzero(wrong)))
    nlp_ref = _nlp64(t_ref[best_m, cols], ref.dof)
    _, nlp_tol = ref.tolerances(r_ref[best_m, cols])
    nlp_err = np.abs(best_nlp - nlp_ref)
    j = int(nlp_err.argmax())
    if nlp_err[j] > nlp_tol[j]:
        from repro.core.stats import REFINE_WIDTH, refine_neglog10p

        # Split the error: the f32 -log10 p of the exact t, on this device.
        t_j = t_ref[best_m[j], j]
        f32_at_exact_t = refine_neglog10p(np.array([t_j]), ref.dof, width=REFINE_WIDTH)[0]
        raise SmokeFailure(
            f"{name}: per-trait best -log10 p off float64 by {nlp_err[j]:.4g} "
            f"(tolerance {nlp_tol[j]:.4g}) at trait{j}: engine {best_nlp[j]}, "
            f"float64 {nlp_ref[j]:.5f} at t={t_j:.5f}, f32 -log10 p of that t "
            f"{f32_at_exact_t:.5f}")
    t_err = []
    for (m, j), (_, t, _) in read_hits(out).items():
        if j < k:
            t_tol, _ = ref.tolerances(r_ref[m, j])
            err = abs(t - t_ref[m, j])
            check(err <= t_tol, f"{name}: hit (rs{m:08d}, trait{j}) t={t} "
                                f"vs float64 {t_ref[m, j]:.5f} (tolerance {t_tol:.3g})")
            t_err.append(err)
    errs = {"best_nlp_max_err": float(nlp_err.max()),
            "hit_t_max_err": float(max(t_err, default=0.0)),
            "best_marker_ties": int(((best_m != top) & ~wrong).sum())}
    print(f"  {name} vs float64 ({k} traits): {errs}", flush=True)
    return errs


def check_planted(name: str, hits: dict, cohort) -> None:
    missing = [(m, t) for m, t, _ in cohort.effects if (m, t) not in hits]
    check(not missing, f"{name}: planted effects missing from hits.tsv: {missing}")


def check_calibration(name: str, summary: dict, out: str, cohort,
                      batches: int) -> None:
    """lambda_gc, and the per-trait best p of traits with no planted
    effect: with M independent null markers, u = 1 - (1 - p_min)^M is
    uniform, so its median must sit within 4 standard errors of 1/2."""
    # The lambda probe is 64 t values of trait 0 per batch; the median of n
    # chi2(1) draws has relative standard error ~2.33/sqrt(n).
    n_probe = 64 * batches
    lam = summary["lambda_gc"]
    band = 4 * 2.33 / np.sqrt(n_probe)
    check(abs(lam - 1.0) <= band,
          f"{name}: lambda_gc {lam:.4f} outside 1 +- {band:.3f} ({n_probe} probe values)")
    _, nlp = read_best(out)
    planted = {t for _, t, _ in cohort.effects}
    null = np.array([v for j, v in enumerate(nlp) if j not in planted])
    m = cohort.dosages.shape[0]
    u = -np.expm1(m * np.log1p(-(10.0 ** -null)))
    med = float(np.median(u))
    check(abs(med - 0.5) <= 4 * 0.5 / np.sqrt(len(null)),
          f"{name}: median best-p uniform {med:.4f} over {len(null)} null traits")
    print(f"  {name}: lambda_gc {lam:.4f} (1 +- {band:.3f}), "
          f"best-p uniform median {med:.4f} over {len(null)} null traits", flush=True)


def check_panel(ref: OlsReference) -> None:
    """The device's covariate residualization (Eq. 1) against float64."""
    import jax.numpy as jnp

    from repro.core.residualize import covariate_basis, residualize_and_standardize

    q = covariate_basis(jnp.asarray(ref.c_raw, jnp.float32), ref.n)
    panel = residualize_and_standardize(jnp.asarray(ref.y_raw, jnp.float32), q).y
    err = float(np.abs(np.asarray(panel, np.float64) - ref.y).max())
    print(f"  residualized panel vs float64: max |dy| {err:.3g} "
          f"(tolerance {PANEL_TOL:g})", flush=True)
    check(err <= PANEL_TOL, f"residualized panel |dy| {err:.3g} > {PANEL_TOL:g}")


def check_kernel_r(cohort, ref: OlsReference, sizes: Sizes) -> float:
    """The fused kernel alone, at the scan's blocks, against float64 r."""
    import jax.numpy as jnp

    from repro.kernels.gwas_dot import ops as kops

    m = min(sizes.kernel_markers, cohort.dosages.shape[0])
    d = cohort.dosages[:m]
    codes = np.select([d == 2, d == 1, d == 0], [0b00, 0b10, 0b11], 0b01).astype(np.uint8)
    mean, inv_std, _ = kops.marker_stats_from_codes(codes)
    y = ref.y.astype(np.float32)
    r, _ = kops.gwas_dot(kops.pack_tiled(codes, 512), mean, inv_std, jnp.asarray(y),
                         n_samples=ref.n, dof=int(ref.dof))
    err = float(np.abs(np.asarray(r, np.float64) - ref.g_std(0, m) @ ref.y / ref.n).max())
    print(f"  fused kernel r vs float64 ({m} markers x {y.shape[1]} traits): "
          f"max |dr| {err:.3g} (tolerance {R_TOL:g})", flush=True)
    check(err <= R_TOL, f"fused kernel |dr| {err:.3g} > {R_TOL:g}")
    return err


def check_hit_sets(fused: dict, dense: dict, ref: OlsReference,
                   threshold: float) -> None:
    """Same hit set, apart from pairs within tolerance of the threshold."""
    for key in set(fused) ^ set(dense):
        r, _, nlp = fused.get(key) or dense[key]
        _, tol = ref.tolerances(np.float64(r))
        check(abs(nlp - threshold) <= tol,
              f"hit {key} (-log10 p {nlp}) only in one engine's hits.tsv")
    print(f"  fused/dense hit sets: {len(fused)} vs {len(dense)}, "
          f"{len(set(fused) ^ set(dense))} differ within tolerance", flush=True)


def check_fused_hlo(sizes: Sizes) -> None:
    """The fused step at the scan's shapes holds the compiled Mosaic kernel."""
    import jax
    import jax.numpy as jnp

    from repro.core.association import AssocOptions
    from repro.core.engines import build_fused_step

    n, p, m = sizes.n_samples, sizes.n_traits, sizes.batch_markers
    n_pad = n + (-n) % 512
    step = build_fused_step(n_samples=n, n_covariates=sizes.n_covariates,
                            options=AssocOptions(), sparse_epilogue=True)
    f32 = jnp.float32
    hlo = step.lower(
        jax.ShapeDtypeStruct((m, n_pad // 4), jnp.uint8),
        jax.ShapeDtypeStruct((m, 1), f32), jax.ShapeDtypeStruct((m, 1), f32),
        jax.ShapeDtypeStruct((m,), jnp.bool_), jax.ShapeDtypeStruct((n, p), f32),
    ).compile().as_text()
    check("tpu_custom_call" in hlo, "no tpu_custom_call in the fused step's HLO")
    print("  fused step HLO holds tpu_custom_call", flush=True)


def check_screen_kernel(sizes: Sizes, seed: int) -> None:
    """``screen_compact`` on the device against numpy on its own t."""
    import jax.numpy as jnp

    from repro.kernels.tstat import screen_compact

    rng = np.random.default_rng(seed)
    r = rng.uniform(-0.12, 0.12, size=(sizes.batch_markers, sizes.lmm_traits)).astype(np.float32)
    dof, t2, cap = float(sizes.lmm_samples - 2 - sizes.n_covariates), 30.0, 4096
    t, idx, count = screen_compact(jnp.asarray(r), dof, t2, cap)
    t = np.asarray(t)
    t64 = r * np.sqrt(dof / (1.0 - r.astype(np.float64) ** 2))
    check(np.allclose(t, t64, rtol=1e-5, atol=1e-5), "screen kernel t off numpy")
    want = np.flatnonzero((t * t).ravel() >= t2)
    got = np.asarray(idx)
    check(int(count) == want.size, f"screen count {int(count)} != {want.size}")
    k = min(cap, want.size)
    check((got[:k] == want[:k]).all() and (got[k:] == -1).all(),
          "screen hit indices differ from numpy")
    print(f"  screen kernel: {want.size} survivors of {r.size}, t max rel err "
          f"{float(np.max(np.abs(t - t64) / np.maximum(np.abs(t64), 1))):.2g}", flush=True)


# ------------------------------------------------------------------ phases


def run_one_chip(sizes: Sizes, seed: int, work: str, phase: Phases) -> None:
    threshold = 7.301
    batches = -(-sizes.n_markers // sizes.batch_markers)
    grid = ["--batch-markers", str(sizes.batch_markers)]
    with phase("cohort"):
        cohort, paths = write_cohort(
            sizes, seed, os.path.join(work, "cohort"), n_samples=sizes.n_samples,
            n_traits=sizes.n_traits, effect_size=sizes.effect_size)
    out_f, out_d = os.path.join(work, "fused"), os.path.join(work, "dense")
    with phase("fused scan"):
        s_f = scan(paths, out_f, "--engine", "fused", *grid)
    with phase("dense scan"):
        s_d = scan(paths, out_d, "--engine", "dense", *grid)
    with phase("check"):
        hits_f, hits_d = read_hits(out_f), read_hits(out_d)
        check_planted("fused", hits_f, cohort)
        check_planted("dense", hits_d, cohort)
        check_calibration("fused", s_f, out_f, cohort, batches)
        check_calibration("dense", s_d, out_d, cohort, batches)
        ref = OlsReference(cohort, sizes.ref_traits)
        r_ref = ref.r()
        check_panel(ref)
        check_kernel_r(cohort, ref, sizes)
        check_against_reference("dense", out_d, ref, r_ref)
        check_against_reference("fused", out_f, ref, r_ref)
        check_hit_sets(hits_f, hits_d, ref, threshold)
        check_fused_hlo(sizes)
    with phase("lmm scan"):
        lmm_cohort, lmm_paths = write_cohort(
            sizes, seed + 1, os.path.join(work, "lmm"), n_samples=sizes.lmm_samples,
            n_traits=sizes.lmm_traits, effect_size=sizes.lmm_effect_size)
        out_l = os.path.join(work, "lmm_out")
        s_l = scan(lmm_paths, out_l, "--engine", "lmm", "--lmm-epilogue", "fused", *grid)
        check_planted("lmm", read_hits(out_l), lmm_cohort)
        check_calibration("lmm", s_l, out_l, lmm_cohort, batches)
        check_screen_kernel(sizes, seed)


def run_four_chips(sizes: Sizes, seed: int, work: str, phase: Phases) -> None:
    # Eight single-batch leases, so that all four slots get work.
    grid = ["--engine", "fused", "--batch-markers", str(sizes.n_markers // 8),
            "--lease-batches", "1"]
    with phase("cohort"):
        _, paths = write_cohort(
            sizes, seed, os.path.join(work, "cohort"), n_samples=sizes.n_samples,
            n_traits=sizes.n_traits, effect_size=sizes.effect_size)
    outs = {}
    for devices in (4, 1):
        outs[devices] = os.path.join(work, f"devices{devices}")
        with phase(f"fused scan --devices {devices}"):
            summary = scan(paths, outs[devices], *grid, "--devices", str(devices))
        if devices == 4:
            executor = summary["executor"]
    for name in ("hits.tsv", "per_trait_best.tsv", "qc.tsv"):
        with open(os.path.join(outs[4], name), "rb") as a, \
                open(os.path.join(outs[1], name), "rb") as b:
            check(a.read() == b.read(), f"--devices 4 {name} differs from --devices 1")
    print("  hits.tsv, per_trait_best.tsv, qc.tsv byte-identical across 4 and 1 devices")
    workers = executor["workers"]
    for w, st in workers.items():
        print(f"  {w}: claimed {st['claimed']} completed {st['completed']} "
              f"arrays on {executor['slot_devices'].get(w)}", flush=True)
    check(len(workers) == 4 and all(st["claimed"] > 0 for st in workers.values()),
          f"not every one of 4 workers claimed cells: {workers}")
    placed = [tuple(v) for v in executor["slot_devices"].values()]
    check(len(set(placed)) == 4 and all(len(v) == 1 for v in placed),
          f"slots do not sit on four distinct devices: {executor['slot_devices']}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0, help="cohort seed")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the --devices 4 vs --devices 1 executor check")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"chip_smoke: no src/repro next to {__file__}; run it from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform} "
              f"({dev.device_kind}); no CPU fallback", file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} chips, JAX found {len(devices)}",
              file=sys.stderr)
        return 1

    from repro.runtime.compile_cache import enable_compile_cache

    print(f"device: {dev.device_kind} x {len(devices)}, jax {jax.__version__}, "
          f"compile cache {enable_compile_cache()}", flush=True)
    phase = Phases()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as work:
        if args.four_chips:
            run_four_chips(Sizes(), args.seed, work, phase)
        else:
            run_one_chip(Sizes(), args.seed, work, phase)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
