"""Benchmark harness — one function per paper table/figure.

    concordance   — Fig. 2 left: engine vs per-trait OLS (Pearson of -log10 p)
    throughput    — Fig. 2 right / §3.2: wall time vs panel width P, panel
                    engine vs per-trait loop (the fastGWA-usage analogue)
    engines       — dense (paper-faithful) vs fused 2-bit path, equal stats
    lmm           — mixed-model wing: GRM/eigen/REML setup amortization vs
                    the per-marker rotation overhead (the fastGWA analogue)
    trait_block   — 2-D scan grid sweep: wall time + peak panel residency
                    vs trait-block width (device memory bounded by the
                    block, not the panel; statistics bitwise-identical;
                    warm-measured — see the §10 compile-time note)
    executor      — multi-device grid executor sweep (fake CPU devices in a
                    subprocess): device count x placement, per-device
                    utilization from the session metrics, bitwise identity
    pipeline      — per-slot pipelining before/after (§15): unpipelined vs
                    prefetched/double-buffered workers at 2 and 4 devices,
                    decode/stage shares of step time
    serve         — scan-as-a-service (§16): warm window-query latency
                    p50/p95/p99 through the full request path (admission,
                    fair-share queue, resident-state reuse), cold-query
                    cost, and 2-client concurrent panel throughput
    kernels       — us/call of the association GEMM across batch geometries
    scaling_n     — runtime vs cohort size N (linear, §2.2)

Run with ``--sections serve,kernels`` to re-measure a subset; rows for the
other sections are carried over from the existing ``BENCH_scan.json``.

Prints ``name,us_per_call,derived`` CSV rows and writes the same data as
``BENCH_scan.json`` (per-section us/call + derived metrics) so the perf
trajectory is machine-diffable across PRs.  CPU numbers contextualize the
*shape* of the paper's claims (sub-linear P scaling, engine equivalence);
absolute TPU throughput comes from the dry-run roofline (EXPERIMENTS.md).
"""
from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from scipy import stats as sps

from repro.core import association as A
from repro.core import residualize as Rz
from repro.core import stats as S
from repro.core.screening import GenomeScan, ScanConfig
from repro.io import plink, synth

ROWS: list[dict] = []
_SECTION = "misc"


def emit(name: str, us_per_call: float, derived: str) -> None:
    ROWS.append(
        {"section": _SECTION, "name": name, "us_per_call": round(us_per_call, 1),
         "derived": derived}
    )
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)


def _timeit(fn, *args, repeats=3):
    out = fn(*args)  # compile / warm
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / repeats * 1e6, out


def bench_concordance() -> None:
    """Paper Fig. 2 left: near-perfect agreement with per-trait OLS."""
    co = synth.make_cohort(n_samples=500, n_markers=300, n_traits=8,
                           n_causal=6, effect_size=0.5, seed=1)
    n, q = 500, co.covariates.shape[1]
    qb = Rz.covariate_basis(jnp.asarray(co.covariates), n)
    panel = Rz.residualize_and_standardize(jnp.asarray(co.phenotypes), qb)
    res, _ = A.assoc_batch(
        jnp.asarray(co.dosages.astype(np.float32)), panel.y,
        n_samples=n, n_covariates=q,
    )
    g_std, _ = A.standardize_genotype_batch(jnp.asarray(co.dosages.astype(np.float32)))
    g_std = np.asarray(g_std)
    yr = np.asarray(panel.y)
    ref = np.empty((300, 8), np.float64)
    for m in range(300):
        for p in range(8):
            ref[m, p] = sps.linregress(g_std[m], yr[:, p]).rvalue
    r_pearson = np.corrcoef(np.asarray(res.r).ravel(), ref.ravel())[0, 1]
    emit("concordance_fig2_left", 0.0, f"pearson_r={r_pearson:.6f}")


def bench_throughput() -> None:
    """Paper Fig. 2 right: runtime vs phenotype count, panel vs per-trait.

    Two pipelines are timed: the scan core (GEMM + t statistics — on the
    paper's GPU/our TPU target this is the whole cost) and the full
    default pipeline including -log10 p, which since §13 screens every
    lane on t^2, compacts the rare survivors, and refines only those
    through the canonical host-side executables.  The dense full-tile CF
    that used to put the epilogue at 94-99 % of wall time is measured in
    the ``epilogue`` section for the before/after record."""
    n, m = 2_000, 4_096
    rng = np.random.default_rng(0)
    g = rng.binomial(2, 0.3, size=(m, n)).astype(np.float32)
    g_dev, _ = A.standardize_genotype_batch(jnp.asarray(g))
    g_dev = jax.block_until_ready(g_dev)

    core_opts = A.AssocOptions(compute_neglog10p=False)
    dof = A.AssocOptions().dof(n, 0)
    plan = A.plan_sparse_epilogue(7.301, dof)

    @jax.jit
    def core_scan(g_std, y_std):
        return A.assoc_from_standardized(
            g_std, y_std, n_samples=n, n_covariates=0, options=core_opts
        )

    @jax.jit
    def sparse_step(g_std, y_std):
        res = A.assoc_from_standardized(
            g_std, y_std, n_samples=n, n_covariates=0, options=core_opts
        )
        return A.sparse_epilogue_outputs(res.r, res.t, dof, plan)

    def full_scan(g_std, y_std):
        # The default scan pipeline: core + t^2 screen/compact on device +
        # the canonical exact-tail refine host-side (DESIGN.md §13).
        out = sparse_step(g_std, y_std)
        hit_nlp = S.refine_neglog10p(np.asarray(out["hit_t"]), dof)
        best_nlp = S.refine_neglog10p(np.asarray(out["batch_best_t"]), dof)
        return hit_nlp, best_nlp

    qb = Rz.covariate_basis(None, n)
    base_us = base_p = None
    us_core = 0.0
    for p in [64, 256, 1024, 2048]:
        y = rng.normal(size=(n, p)).astype(np.float32)
        panel = Rz.residualize_and_standardize(jnp.asarray(y), qb)
        us_core, _ = _timeit(core_scan, g_dev, panel.y)
        us_full, _ = _timeit(full_scan, g_dev, panel.y)
        if base_us is None:
            base_us, base_p = us_core, p
        emit(f"throughput_core_P{p}", us_core, f"us_per_phenotype={us_core / p:.2f}")
        emit(f"throughput_full_P{p}", us_full, f"pvalue_epilogue_share={1 - us_core / max(us_full, 1):.2f}")
    emit("throughput_sublinearity_core", 0.0,
         f"grew_{us_core / base_us:.1f}x_for_{2048 // base_p}x_phenotypes")

    # per-trait loop (fastGWA usage pattern): one trait per scan
    y1 = rng.normal(size=(n, 1)).astype(np.float32)
    panel1 = Rz.residualize_and_standardize(jnp.asarray(y1), qb)
    us1, _ = _timeit(core_scan, g_dev, panel1.y)
    emit("per_trait_loop_core", us1,
         f"panel_speedup_at_P2048={us1 * 2048 / us_core:.0f}x")


def bench_engines() -> None:
    """dense vs fused engine on the same cohort: identical statistics.
    (CPU wall-time of the fused path runs the Pallas interpreter and is not
    indicative of TPU perf — see EXPERIMENTS.md §Roofline for the real
    comparison; here we verify equivalence and report timings for record.)"""
    import os
    import tempfile

    co = synth.make_cohort(n_samples=512, n_markers=1024, n_traits=64, seed=3)
    d = tempfile.mkdtemp()
    paths = synth.write_cohort_files(co, os.path.join(d, "bench"))
    src = plink.PlinkBed(paths["bed"])
    results = {}
    for engine in ("dense", "fused"):
        cfg = ScanConfig(batch_markers=512, engine=engine,
                         block_m=64, block_n=128, block_p=64)
        t0 = time.perf_counter()
        res = GenomeScan(src, co.phenotypes, co.covariates, config=cfg).run()
        dt = time.perf_counter() - t0
        results[engine] = res
        emit(f"engine_{engine}_scan", dt * 1e6,
             f"markers_per_s={co.dosages.shape[0] / dt:.0f}")
    agree = np.abs(results["dense"].best_nlp - results["fused"].best_nlp).max()
    emit("engine_agreement", 0.0, f"max_abs_dnlp={agree:.2e}")


def bench_lmm() -> None:
    """Mixed-model wing: one-time setup (GRM stream + eigendecomposition +
    REML) vs the steady-state scan.  The derived columns are the ones that
    matter for capacity planning: setup amortizes over the whole genome, the
    rotation GEMM is the per-marker overhead vs the OLS scan."""
    import os
    import tempfile

    co = synth.make_structured_cohort(
        n_samples=512, n_markers=2048, n_traits=32, n_pops=3, fst=0.1,
        h2=0.4, n_causal=4, seed=7,
    )
    d = tempfile.mkdtemp()
    synth.write_split_plink(co, os.path.join(d, "bench"), n_shards=4)
    from repro.io import open_genotypes

    src = open_genotypes(os.path.join(d, "bench_chr*.bed"))
    m = co.dosages.shape[0]

    base = dict(batch_markers=512, block_m=64, block_n=128, block_p=64)
    ols = GenomeScan(src, co.phenotypes, co.covariates,
                     config=ScanConfig(engine="dense", **base))
    t0 = time.perf_counter()                     # scan only: comparable to
    res_ols = ols.run()                          # the lmm_*_scan rows below
    dt_ols = time.perf_counter() - t0
    emit("lmm_baseline_ols_scan", dt_ols * 1e6, f"lambda_gc={res_ols.lambda_gc:.3f}")

    for loco in (False, True):
        tag = "loco" if loco else "global"
        t0 = time.perf_counter()
        scan = GenomeScan(src, co.phenotypes, co.covariates,
                          config=ScanConfig(engine="lmm", loco=loco, **base))
        dt_setup = time.perf_counter() - t0          # GRM + eigh + REML + rotation
        t0 = time.perf_counter()
        res = scan.run()
        dt_scan = time.perf_counter() - t0
        emit(f"lmm_{tag}_setup", dt_setup * 1e6,
             f"scopes={res.lmm_info['scopes']}")
        emit(f"lmm_{tag}_scan", dt_scan * 1e6,
             f"markers_per_s={m / dt_scan:.0f}")
        emit(f"lmm_{tag}_overhead_vs_ols", 0.0,
             f"scan_slowdown={dt_scan / dt_ols:.2f}x,lambda_gc={res.lambda_gc:.3f}")


def bench_trait_blocks() -> None:
    """The 2-D (marker x trait-block) scan grid: wall time and panel
    residency across block widths.  The derived column that matters for
    capacity planning is ``resident_panel_mib`` — the peak device bytes the
    panel can pin (LRU capacity x N x block width x 4), which is bounded by
    the block size rather than the panel width P; ``panel_mib`` is what the
    unblocked scan pins.  Statistics are bitwise-identical across rows
    (asserted here, property-tested in tests/test_traitblocks.py).

    Each width is scanned twice and the WARM run reported: every block
    width compiles its own step (the epilogue tile shape changes), and
    that one-time XLA compile grows with the tile — timing the first run
    made wider blocks look slower at equal grid area when their steady
    state is identical (the historical trait_block_128 "regression"; see
    DESIGN.md §10).  ``cold_extra_ms`` keeps the compile cost visible."""
    import os
    import tempfile

    co = synth.make_cohort(n_samples=512, n_markers=1024, n_traits=256,
                           n_causal=6, seed=5)
    d = tempfile.mkdtemp()
    paths = synth.write_cohort_files(co, os.path.join(d, "bench_tb"))
    src = plink.PlinkBed(paths["bed"])
    n, p = co.phenotypes.shape
    resident_cap = 4
    base = dict(batch_markers=256, block_m=64, block_n=128, block_p=32,
                panel_resident_blocks=resident_cap)
    ref = None
    for tb in (0, 32, 64, 128):
        cfg = ScanConfig(trait_block=tb, **base)
        t0 = time.perf_counter()
        GenomeScan(src, co.phenotypes, co.covariates, config=cfg).run()
        dt_cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        scan = GenomeScan(src, co.phenotypes, co.covariates, config=cfg)
        res = scan.run()
        dt = time.perf_counter() - t0
        if ref is None:
            ref = res
        else:
            assert np.array_equal(ref.best_nlp, res.best_nlp), "grid changed stats"
        width = max(b.n_traits for b in scan.trait_blocks)
        resident = min(resident_cap, scan.n_trait_blocks) * n * width * 4
        emit(
            f"trait_block_{tb or 'off'}", dt * 1e6,
            f"grid={scan.n_batches}x{scan.n_trait_blocks},"
            f"resident_panel_mib={resident / 2**20:.2f},"
            f"panel_mib={n * p * 4 / 2**20:.2f},"
            f"cold_extra_ms={max(dt_cold - dt, 0.0) * 1e3:.0f}",
        )


_EXECUTOR_CHILD = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import json, tempfile, time
import os.path as osp
import numpy as np
import jax
from repro.runtime.compile_cache import enable_compile_cache
# Persistent compile cache: each executor slot jits its own step (the
# prolog memo is keyed per device), so fake devices 1..3 would recompile
# the identical HLO (~0.4 s each).  The cache deserializes device 0's
# executable instead — the sweep measures scheduling and pipelining, not
# XLA compile times.
enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
from repro.api import ExecSpec, GridSpec, Study
from repro.core.sinks import BestTraitSink
from repro.io import plink, synth

co = synth.make_cohort(n_samples=512, n_markers=2048, n_traits=64,
                       n_causal=6, seed=5)
d = tempfile.mkdtemp()
paths = synth.write_cohort_files(co, osp.join(d, "bench_md"))
study = Study.from_arrays(plink.PlinkBed(paths["bed"]),
                          co.phenotypes, co.covariates)
grid = GridSpec(batch_markers=256, trait_block=16,
                block_m=64, block_n=128, block_p=16)

def run(devices, placement, slot_prefetch, autotune):
    session = study.plan(
        grid=grid, hit_threshold_nlp=2.0,
        executor=ExecSpec(devices=devices, placement=placement,
                          slot_prefetch=slot_prefetch,
                          autotune_lease=autotune),
    ).run()
    sink = BestTraitSink(study.n_traits)
    t0 = time.perf_counter()
    for cell in session.events():
        sink.on_cell(cell)
    dt = time.perf_counter() - t0
    key = sink.best_nlp.tobytes() + sink.best_marker.tobytes()
    return dt, key, session.metrics.summary(), session.executor_info

rows, ref = {"executor": [], "pipeline": []}, None
for devices, placement in [(1, "marker-major"), (2, "marker-major"),
                           (4, "marker-major"), (4, "trait-major")]:
    run(devices, placement, 1, True)   # warm page + compile caches
    dt, key, m, info = run(devices, placement, 1, True)
    ref = key if ref is None else ref
    # Two utilization views: the scheduler's busy/(busy+wait) accounting
    # (time holding >=1 claimed item vs empty-handed — DESIGN.md §15) and
    # the per-cell busy_s/wall from the metrics block.  On fake devices
    # timesharing one core the latter is distorted (concurrent steps
    # inflate each other's wall, so it can exceed 1); the scheduler view
    # is the meaningful one here.
    workers = info.get("workers") or {}
    shares = [
        w["busy_s"] / max(w["busy_s"] + w["wait_s"], 1e-9)
        for w in workers.values()
    ]
    rows["executor"].append({
        "devices": devices, "placement": placement, "wall_s": round(dt, 3),
        "markers_per_s": m["markers_per_s"],
        "trait_markers_per_s": m["trait_markers_per_s"],
        "mean_utilization": round(sum(shares) / len(shares), 3) if shares
        else round(
            sum(v["utilization"] for v in m["per_device"].values())
            / max(len(m["per_device"]), 1), 3),
        "cell_util": round(
            sum(v["utilization"] for v in m["per_device"].values())
            / max(len(m["per_device"]), 1), 3),
        "final_lease": (info.get("autotune") or {}).get("final_lease"),
        "identical_to_serial": key == ref,
    })
for devices in (2, 4):
    for piped in (0, 1):
        dt, key, m, info = run(devices, "marker-major", piped, bool(piped))
        rows["pipeline"].append({
            "devices": devices, "slot_prefetch": piped,
            "wall_s": round(dt, 3),
            "trait_markers_per_s": m["trait_markers_per_s"],
            "decode_s": m["decode_s"], "stage_s": m["stage_s"],
            "step_s": m["step_s"],
            "identical_to_serial": key == ref,
        })
print(json.dumps(rows))
"""

_MD_ROWS: dict | None = None


def _executor_child_rows() -> dict:
    """Run the 4-fake-device subprocess once; both the ``executor`` and
    ``pipeline`` sections read from its output."""
    global _MD_ROWS
    if _MD_ROWS is not None:
        return _MD_ROWS
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", _EXECUTOR_CHILD],
        capture_output=True, text=True, timeout=1800, env=env,
    )
    if proc.returncode != 0:
        raise SystemExit(
            f"executor sweep child failed (exit {proc.returncode}):\n"
            f"{proc.stderr.strip()[-3000:]}"
        )
    _MD_ROWS = json.loads(proc.stdout.strip().splitlines()[-1])
    return _MD_ROWS


def bench_executor() -> None:
    """Multi-device grid executor sweep (DESIGN.md §12), on 4 fake CPU
    devices in a subprocess (the device count is fixed at process start).
    Fake devices timeshare ONE physical CPU, so wall time here measures
    scheduling/staging overhead, not speedup — the rows that matter are
    per-device utilization (the executor keeps slots busy), the session
    metrics throughput, and ``identical=True`` (bitwise identity across
    device counts and placements, the §12 contract).  Each config is run
    twice and the warm run reported (first-touch page-cache and compile-
    cache costs are not scheduling overhead)."""
    for row in _executor_child_rows()["executor"]:
        emit(
            f"executor_d{row['devices']}_{row['placement'].replace('-', '_')}",
            row["wall_s"] * 1e6,
            f"trait_markers_per_s={row['trait_markers_per_s']:.0f},"
            f"mean_util={row['mean_utilization']},"
            f"final_lease={row['final_lease']},"
            f"identical={row['identical_to_serial']}",
        )


def bench_pipeline() -> None:
    """Per-slot pipelining before/after (DESIGN.md §15): the same grid
    drained with ``slot_prefetch=0`` (the historical one-staged-batch
    worker, autotune off) vs the pipelined default at 2 and 4 devices.
    ``decode_share``/``stage_share`` are host decode and H2D staging time
    as fractions of total device step time — the pipelined rows overlap
    them with compute, the unpipelined rows pay them on the critical
    path.  Outputs are bitwise-identical across all rows."""
    for row in _executor_child_rows()["pipeline"]:
        step = max(row["step_s"], 1e-9)
        tag = "piped" if row["slot_prefetch"] else "unpiped"
        emit(
            f"pipeline_d{row['devices']}_{tag}",
            row["wall_s"] * 1e6,
            f"trait_markers_per_s={row['trait_markers_per_s']:.0f},"
            f"decode_share={row['decode_s'] / step:.3f},"
            f"stage_share={row['stage_s'] / step:.3f},"
            f"identical={row['identical_to_serial']}",
        )


def bench_epilogue() -> None:
    """§13 before/after on one statistic tile (M=4096, P=2048): the dense
    128-trip CF over every lane (the historical default, 94-99 % of scan
    wall time on CPU) vs the t^2 screen + compact + canonical refine the
    scan now runs.  ``share_of_full`` is each epilogue's fraction of a
    (core + epilogue) step — the sparse row is the acceptance number."""
    n, m, p = 2_000, 4_096, 2_048
    rng = np.random.default_rng(0)
    g = rng.binomial(2, 0.3, size=(m, n)).astype(np.float32)
    g_dev, _ = A.standardize_genotype_batch(jnp.asarray(g))
    y = rng.normal(size=(n, p)).astype(np.float32)
    panel = Rz.residualize_and_standardize(
        jnp.asarray(y), Rz.covariate_basis(None, n)
    )
    core_opts = A.AssocOptions(compute_neglog10p=False)
    dof = A.AssocOptions().dof(n, 0)

    @jax.jit
    def core(g_std, y_std):
        return A.assoc_from_standardized(
            g_std, y_std, n_samples=n, n_covariates=0, options=core_opts
        )

    us_core, res = _timeit(core, g_dev, panel.y)
    r_tile = jax.block_until_ready(res.r)
    t_tile = jax.block_until_ready(res.t)

    @jax.jit
    def dense_cf(t):
        return S.neglog10_p_from_t(t, dof)

    us_dense, _ = _timeit(dense_cf, t_tile, repeats=1)

    plan = A.plan_sparse_epilogue(7.301, dof)

    @jax.jit
    def screen(r, t):
        return A.sparse_epilogue_outputs(r, t, dof, plan)

    def sparse_ep(r, t):
        out = screen(r, t)
        hit_nlp = S.refine_neglog10p(np.asarray(out["hit_t"]), dof)
        best_nlp = S.refine_neglog10p(np.asarray(out["batch_best_t"]), dof)
        return out, hit_nlp, best_nlp

    us_sparse, (out, _, _) = _timeit(sparse_ep, r_tile, t_tile)
    emit("epilogue_dense_cf", us_dense,
         f"share_of_full={us_dense / (us_core + us_dense):.2f}")
    emit("epilogue_sparse", us_sparse,
         f"share_of_full={us_sparse / (us_core + us_sparse):.2f},"
         f"speedup_vs_dense={us_dense / max(us_sparse, 1):.0f}x")
    emit("epilogue_compaction", 0.0,
         f"screen_count={int(out['screen_count'])},capacity={plan.capacity},"
         f"lanes={m * p}")


def bench_io() -> None:
    """Packed genotype staging (DESIGN.md §17): the same scan drained with
    dense float32 staging vs 2-bit packed bytes as the H2D currency.  Wall
    time on CPU is not the point (fake-device H2D is a memcpy); the rows
    that matter are ``h2d_bytes_per_marker`` — ceil(N/4) packed vs 4N
    dense, the ~16x reduction the acceptance gate checks — ``decode_s``
    (host prep collapses to a slab memcpy + stat LUTs), and
    ``identical=True`` (packed staging is bitwise-neutral).  The cache row
    re-runs the packed scan against a warm ``PackedSlabCache``: every slab
    is a hit, so host prep pays zero disk reads."""
    import os
    import tempfile

    from repro.api import GridSpec, IOSpec, Study, TsvWriter
    from repro.io import open_genotypes
    from repro.io.packed_cache import default_cache

    co = synth.make_cohort(
        n_samples=1003, n_markers=2048, n_traits=32, missing_rate=0.02, seed=5
    )
    d = tempfile.mkdtemp()
    beds = synth.write_split_plink(co, os.path.join(d, "bench"), n_shards=3)
    src = open_genotypes(",".join(beds))
    study = Study.from_arrays(src, co.phenotypes, co.covariates)
    grid = GridSpec(batch_markers=512, block_m=64, block_n=128, block_p=64)

    def scan(tag, staging):
        default_cache().clear()
        plan = study.plan(grid=grid, io=IOSpec(genotype_staging=staging),
                          hit_threshold_nlp=2.0)
        t0 = time.perf_counter()
        session = plan.run()
        out = os.path.join(d, tag)
        session.stream_to(TsvWriter(out))
        dt = time.perf_counter() - t0
        files = {
            f: open(os.path.join(out, f)).read()
            for f in ("hits.tsv", "per_trait_best.tsv", "qc.tsv")
        }
        return dt, session.metrics.summary(), files

    dt_d, m_d, files_d = scan("stage_dense", "dense")
    dt_p, m_p, files_p = scan("stage_packed", "packed")
    emit("io_dense_staging", dt_d * 1e6,
         f"h2d_bytes_per_marker={m_d['h2d_bytes_per_marker']:.0f},"
         f"decode_s={m_d['decode_s']:.3f}")
    emit("io_packed_staging", dt_p * 1e6,
         f"h2d_bytes_per_marker={m_p['h2d_bytes_per_marker']:.0f},"
         f"decode_s={m_p['decode_s']:.3f},"
         f"identical={files_p == files_d}")
    emit("io_h2d_reduction", 0.0,
         f"bytes_ratio={m_d['h2d_bytes_per_marker'] / m_p['h2d_bytes_per_marker']:.1f}x,"
         f"n_samples={co.phenotypes.shape[0]}")

    # Warm-cache rerun: the whole genotype stream is slab-cache hits.
    plan = study.plan(grid=grid, io=IOSpec(genotype_staging="packed"),
                      hit_threshold_nlp=2.0)
    t0 = time.perf_counter()
    session = plan.run()
    session.stream_to(TsvWriter(os.path.join(d, "stage_packed_warm")))
    dt_w = time.perf_counter() - t0
    cs = default_cache().stats()
    emit("io_packed_warm_cache", dt_w * 1e6,
         f"cache_hits={cs['hits']},cache_misses={cs['misses']},"
         f"decode_s={session.metrics.summary()['decode_s']:.3f}")


def bench_serve() -> None:
    """Scan-as-a-service (DESIGN.md §16): request latency through the full
    serve path — admission, fair-share queueing on the persistent
    WorkQueue, resident-state reuse, request-scoped TSV writers.  The row
    that matters for an interactive service is the WARM window-query
    latency: the resident study already holds the residualized panel,
    compiled step, and device slots, so a query pays only decode + step +
    epilogue + write.  ``serve_window_cold`` keeps the one-time cost
    (first decode/compile for the window shape) visible, and
    ``serve_concurrent_panels`` measures two interleaved panel uploads
    sharing the executor — the multi-tenant case."""
    import os
    import tempfile

    from repro.api import GridSpec, Study
    from repro.serve import ServeHost

    co = synth.make_cohort(n_samples=512, n_markers=2048, n_traits=64,
                           n_causal=6, seed=9)
    d = tempfile.mkdtemp()
    paths = synth.write_cohort_files(co, os.path.join(d, "bench_serve"))
    study = Study.from_files(paths["bed"], paths["pheno"], paths["cov"])
    host = ServeHost(devices=1, max_resident_slots=4,
                     out_root=os.path.join(d, "serve_out"))
    try:
        host.admit_study(
            "bench", study,
            grid=GridSpec(batch_markers=256, trait_block=16,
                          block_m=64, block_n=128, block_p=16),
            hit_threshold_nlp=2.0,
        )
        warm = host.warm_study("bench")
        emit("serve_warm_study", warm["prepare_s"] * 1e6,
             "one_time=source_scan+residualize+compile")

        def window(lo: int, hi: int) -> float:
            t0 = time.perf_counter()
            info = host.wait(host.submit_window("bench", lo, hi), timeout=600)
            assert info["status"] == "done", info
            return time.perf_counter() - t0

        cold_s = window(0, 256)  # first query still pays step compile
        lats = []
        m_total = co.dosages.shape[0]
        for i in range(15):
            lo = (i * 256) % m_total
            lats.append(window(lo, lo + 256))
        p50, p95, p99 = (float(np.percentile(lats, q)) for q in (50, 95, 99))
        emit("serve_window_cold", cold_s * 1e6,
             f"first_query_extra_vs_warm_p50={cold_s / max(p50, 1e-9):.1f}x")
        emit("serve_window_warm", float(np.mean(lats)) * 1e6,
             f"n=15,p50_ms={p50 * 1e3:.0f},p95_ms={p95 * 1e3:.0f},"
             f"p99_ms={p99 * 1e3:.0f}")

        import threading

        rng = np.random.default_rng(11)
        errs: list[str] = []

        def panel_client(seed_off: int) -> None:
            panel = np.asarray(co.phenotypes) + rng.normal(
                scale=1e-3, size=co.phenotypes.shape
            ).astype(np.float32) * seed_off
            info = host.wait(
                host.submit_panel("bench", panel), timeout=600
            )
            if info["status"] != "done":
                errs.append(str(info))

        t0 = time.perf_counter()
        ts = [threading.Thread(target=panel_client, args=(i,))
              for i in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        dt = time.perf_counter() - t0
        assert not errs, errs
        summary = host.metrics_summary()
        lat = summary["serve"]["latency"]
        cache = summary["serve"]["caches"]["device_state"]
        tm = 2 * m_total * co.phenotypes.shape[1]
        emit("serve_concurrent_panels", dt * 1e6,
             f"requests=2,trait_markers_per_s={tm / dt:.0f},"
             f"device_state_hit_rate={cache['hit_rate']}")
        emit("serve_latency_all", 0.0,
             f"n={lat['n']},p50_s={lat['p50_s']},p95_s={lat['p95_s']},"
             f"p99_s={lat['p99_s']}")
    finally:
        host.shutdown()


def bench_kernels() -> None:
    """Association GEMM across geometries (us/call + achieved GFLOP/s)."""
    rng = np.random.default_rng(0)
    n = 2_000
    for m, p in [(1024, 256), (4096, 256), (1024, 2048)]:
        g = jnp.asarray(rng.normal(size=(m, n)).astype(np.float32))
        y = jnp.asarray(rng.normal(size=(n, p)).astype(np.float32))

        @jax.jit
        def corr(g, y):
            return A.correlation(g, y, n)

        us, _ = _timeit(corr, g, y)
        gflops = 2.0 * m * n * p / (us * 1e-6) / 1e9
        emit(f"gemm_M{m}_P{p}", us, f"gflops={gflops:.1f}")


def bench_scaling_n() -> None:
    rng = np.random.default_rng(0)
    m, p = 2048, 256
    core_opts = A.AssocOptions(compute_neglog10p=False)
    for n in [500, 1000, 2000, 4000]:
        g = jnp.asarray(rng.normal(size=(m, n)).astype(np.float32))
        y = jnp.asarray(rng.normal(size=(n, p)).astype(np.float32))

        def step(g, y, n=n):
            return A.assoc_from_standardized(
                g, y, n_samples=n, n_covariates=0, options=core_opts
            )

        step_j = jax.jit(step)
        us, _ = _timeit(step_j, g, y)
        emit(f"scaling_N{n}", us, f"us_per_sample={us / n:.2f}")


def main(argv: list[str] | None = None) -> None:
    global _SECTION
    import argparse

    sections = [
        ("concordance", bench_concordance),
        ("throughput", bench_throughput),
        ("engines", bench_engines),
        ("lmm", bench_lmm),
        ("trait_block", bench_trait_blocks),
        ("executor", bench_executor),
        ("pipeline", bench_pipeline),
        ("epilogue", bench_epilogue),
        ("io", bench_io),
        ("serve", bench_serve),
        ("kernels", bench_kernels),
        ("scaling_n", bench_scaling_n),
    ]
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--sections", default=None, metavar="A,B,...",
        help="run only these sections and merge the rest from the existing "
             f"BENCH_scan.json (default: all of {','.join(n for n, _ in sections)})",
    )
    args = ap.parse_args(argv)
    wanted = None if args.sections is None else set(args.sections.split(","))
    if wanted:
        unknown = wanted - {n for n, _ in sections}
        if unknown:
            ap.error(f"unknown sections: {sorted(unknown)}")

    print("name,us_per_call,derived")
    for name, fn in sections:
        if wanted is not None and name not in wanted:
            continue
        _SECTION = name
        fn()
    rows = list(ROWS)
    if wanted is not None:
        # Partial run: keep every row of sections we did not re-run, in the
        # canonical section order, so the JSON stays a full snapshot.
        try:
            with open("BENCH_scan.json") as f:
                kept = [r for r in json.load(f)["rows"]
                        if r["section"] not in wanted]
        except (OSError, KeyError, ValueError):
            kept = []
        order = {n: i for i, (n, _) in enumerate(sections)}
        rows = sorted(kept + rows,
                      key=lambda r: order.get(r["section"], len(order)))
    payload = {
        "schema": 1,
        "device": jax.devices()[0].platform,
        "jax": jax.__version__,
        "sections": sorted({r["section"] for r in rows}),
        "rows": rows,
    }
    with open("BENCH_scan.json", "w") as f:
        json.dump(payload, f, indent=1)
    print(f"wrote BENCH_scan.json ({len(rows)} rows)")


if __name__ == "__main__":
    main()
