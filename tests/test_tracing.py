"""The scan's own trace names: host spans (``api.metrics.span``), the
refine counters, and the device scopes the steps lower with."""
from __future__ import annotations

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import GridSpec, Study, TsvWriter
from repro.api.metrics import SPAN_PREFIX, SPANS, ScanMetrics, span
from repro.api.session import MultiDeviceExecutor, SerialExecutor
from repro.core import stats
from repro.core.association import AssocOptions
from repro.core.engines import build_dense_step, build_fused_step, build_lmm_step
from repro.io import plink

GRID = GridSpec(batch_markers=128, block_m=64, block_n=128, block_p=4)


@pytest.fixture(scope="module")
def study(cohort_files, cohort):
    return Study.from_arrays(plink.PlinkBed(cohort_files["bed"]), cohort.phenotypes,
                             cohort.covariates)


def _host_spans(log_dir) -> list[tuple[str, dict]]:
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    return [(e.name, dict(e.stats))
            for plane in ProfileData.from_file(path).planes
            for line in plane.lines for e in line.events
            if e.name.startswith(SPAN_PREFIX)]


def test_a_profiled_scan_emits_every_span_with_its_slot(study, tmp_path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path / "trace"), profiler_options=opts):
        plan = study.plan(engine="fused", grid=GRID)
        session = plan.run(resume=False)
        session.stream_to(TsvWriter(str(tmp_path / "serial")))
        multi = MultiDeviceExecutor(plan.prepare(), n_devices=1)
        plan.run(resume=False, executor=multi).stream_to(TsvWriter(str(tmp_path / "multi")))
    spans = _host_spans(tmp_path / "trace")
    assert {n for n, _ in spans} == {SPAN_PREFIX + s for s in SPANS}
    slots = {a.get("slot") for n, a in spans if n != SPAN_PREFIX + "write"}
    assert slots == {"serial", "dev0"}
    sinks = [a for n, a in spans if n == SPAN_PREFIX + "sinks"]
    assert all(a["refine_launches"] >= 1 for a in sinks)
    # The host totals are the spans' own durations: one clock, one boundary.
    totals = session.metrics.span_totals()
    summary = session.metrics.summary()
    assert summary["extract_s"] == pytest.approx(totals["extract"][0], abs=1e-3)
    assert summary["step_s"] == pytest.approx(
        totals["dispatch"][0] + totals["fence"][0], abs=1e-3)
    assert summary["decode_s"] == pytest.approx(totals["decode"][0], abs=1e-3)
    assert summary["stage_s"] == pytest.approx(totals["stage"][0], abs=1e-3)
    assert totals["write"][1] == summary["live_cells"]
    assert "utilization" not in next(iter(summary["per_device"].values()))


def test_span_folds_from_many_threads():
    """Decode workers, slot and tail threads all fold into one ScanMetrics:
    no fold is lost, even with a switch interval short enough to preempt
    every read-modify-write."""
    import sys
    import threading

    metrics = ScanMetrics()
    n_threads, n_spans = 4 * (os.cpu_count() or 1), 200

    def work():
        for _ in range(n_spans):
            with span("decode", "serial", metrics):
                pass

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    seconds, count = metrics.span_totals()["decode"]
    assert count == n_threads * n_spans and seconds > 0


def _cells(study, **plan):
    prepared = study.plan(engine="dense", grid=GRID, **plan).prepare()
    metrics = ScanMetrics()
    out = list(SerialExecutor(prepared, metrics=metrics).cells(prepared.batches, None))
    for _, timing in out:
        metrics.record(timing)
    return prepared, out, metrics


@pytest.mark.parametrize("capacity", [128, 256])
def test_refine_launches_are_the_chunk_count(study, capacity):
    prepared, out, metrics = _cells(study, hit_capacity=capacity)
    width = stats.REFINE_WIDTH
    screened = [t for _, t in out if t.screen_count > 0]
    assert screened and sum(t.hits for t in screened) > 0
    for cell, t in out:
        hit_chunks = capacity // width if t.screen_count > 0 else 0
        assert not t.overflowed
        assert t.refine_launches == hit_chunks + 1          # + the per-trait bests
        assert t.refine_lanes == hit_chunks * width + cell.n_traits
        assert t.hits == len(cell.hits) and t.foreign_refines == 0
    counters = metrics.counters()
    assert counters["refine_launches"] == sum(t.refine_launches for _, t in out)
    assert counters["hits"] == sum(t.hits for _, t in out)


def test_an_overflowed_cell_refines_its_survivors_in_chunks(study):
    # A low line screens many lanes: past the 64-slot buffer, the host
    # survivor path refines every screened lane in 64-wide chunks.
    prepared, out, metrics = _cells(study, hit_capacity=64, hit_threshold_nlp=1.0)
    over = [t for _, t in out if t.overflowed]
    assert over
    for t in over:
        assert t.screen_count > 64
        assert t.refine_launches == -(-t.screen_count // stats.REFINE_WIDTH) + 1
    assert metrics.counters()["overflowed"] == len(over)


def _text(fn, *args, **kw) -> str:
    return fn.lower(*args, **kw).as_text(debug_info=True)


N, Q, M, P = 96, 2, 64, 8
G = jax.ShapeDtypeStruct((M, N), jnp.float32)
CELL_ARGS = (G, jax.ShapeDtypeStruct((M,), jnp.float32),
             jax.ShapeDtypeStruct((M,), jnp.bool_), jax.ShapeDtypeStruct((N, P), jnp.float32))
RAW = jax.ShapeDtypeStruct((M, N // 4), jnp.uint8)


def _dense():
    return build_dense_step(n_samples=N, n_covariates=Q, options=AssocOptions(),
                            trait_tile=4, sparse_epilogue=True, hit_capacity=64)


def _lmm():
    return build_lmm_step(n_samples=N, n_covariates=Q, options=AssocOptions(),
                          epilogue="fused", block_m=64, block_p=4, sparse_epilogue=True,
                          hit_capacity=64)


def _fused_step():
    fused = build_fused_step(n_samples=N, n_covariates=Q, options=AssocOptions(),
                             block_m=64, block_n=128, block_p=4, sparse_epilogue=True,
                             hit_capacity=64)
    f32 = jax.ShapeDtypeStruct((M, 1), jnp.float32)
    return _text(fused, jax.ShapeDtypeStruct((M, 32), jnp.uint8), f32, f32,
                 CELL_ARGS[2], CELL_ARGS[3])


def _kops():
    from repro.kernels.gwas_dot import ops as kops

    return kops


# Each program of the scan's steps, lowered, and the names its text carries.
PROGRAMS = {
    "dense_prolog": (lambda: _text(_dense().prolog, G),
                     ("jit(gwas_dense_prolog)", "gwas.device_decode")),
    "dense_cell": (lambda: _text(_dense().cell, *CELL_ARGS),
                   ("jit(gwas_dense_cell)", "gwas.assoc", "gwas.epilogue",
                    "gwas.epilogue.best", "gwas.epilogue.compact")),
    "fused_step": (_fused_step, ("jit(gwas_fused_step)", "gwas.assoc", "gwas.epilogue",
                                 "gwas.epilogue.compact", "gwas_dot")),
    "lmm_prolog": (lambda: _text(_lmm().prolog, G, jax.ShapeDtypeStruct((N, N), jnp.float32),
                                 jax.ShapeDtypeStruct((N, 3), jnp.float32)),
                   ("jit(gwas_lmm_prolog)", "gwas.device_decode", "gwas.assoc")),
    "lmm_cell": (lambda: _text(_lmm().cell, *CELL_ARGS),
                 ("jit(gwas_lmm_cell)", "gwas.assoc", "gwas.epilogue")),
    "decode_packed": (lambda: _text(_kops().decode_packed_device, RAW, n_samples=N),
                      ("gwas.device_decode",)),
    "repack_tiled": (lambda: _text(_kops().repack_plink_tiled_device, RAW, n_samples=N,
                                   block_n=128, block_m=64),
                     ("gwas.device_decode",)),
}


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_each_program_lowers_with_its_names(program):
    lower, names = PROGRAMS[program]
    text = lower()
    for name in names:
        assert name in text, name


def test_the_refine_is_one_named_executable():
    text = _text(stats._refine_exe(stats.REFINE_WIDTH, 94.0),
                 jnp.zeros(stats.REFINE_WIDTH, jnp.float32))
    assert "jit_gwas_refine" in text


def test_the_tally_counts_launches_and_lanes():
    tally = stats.RefineTally()
    out = stats.refine_neglog10p(np.linspace(0, 9, 130, dtype=np.float32), 94.0,
                                 width=stats.REFINE_WIDTH, tally=tally)
    assert out.shape == (130,)
    assert (tally.launches, tally.lanes, tally.foreign) == (3, 192, 0)
    stats.refine_neglog10p(np.ones(7, np.float32), 94.0, tally=tally)
    assert (tally.launches, tally.lanes) == (4, 199)
