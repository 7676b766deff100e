"""Run one cell of the chip benchmark once.

Everything a cell needs is found by name: the workload's entry in
``BENCHMARK.json`` names a configuration (its ``file``) and a traffic mix
(``traffic/<mix>.json``); the configuration names its deployment module
(``deployments/<name>.py``, key ``"deployment_module"``, ``ols`` where the
key is absent); and every metric the run reports is read by
``metrics/<metric>.py``.  A later cell, configuration, deployment or
metric is a new file and a new entry; nothing here changes.

One run, each step through the deployment module where it names one:

  set-up   ``make_cohort`` from the seed, ``bind`` it to a ``Study``,
           ``plan(**plan_kwargs(...))``, ``prepare()``, open the session
           and a ``TsvWriter`` in a temporary directory, and pull the first
           cells (warm-up) until every device slot has delivered
           ``warmup_cells_per_device``
  window   pull cells from ``ScanSession.events()`` and write each until
           ``seconds`` have passed; only these cells count
  check    read the device's memory peak, tear the session down, free it,
           and compare what the writer received with the deployment's
           plain ``reference``
"""
from __future__ import annotations

import gc
import glob
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from types import ModuleType

import numpy as np

import compare
import trace_reduce
import trace_scopes
import work
from cohort import seed_sequence

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
SPAN_PREFIX = trace_reduce.SPAN_PREFIX
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
WARMUP_CELL_CAP = 256
DEPLOYMENT_KEY = "deployment_module"
DEFAULT_DEPLOYMENT = "ols"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ------------------------------------------------------------------ files


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    deployment: ModuleType | None = None    # deployments/<name>.py, see load_deployment


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(bench: dict, name: str, *, root: str = ROOT, bench_dir: str = HERE) -> Cell:
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: {sorted(by_name)}")
    wl = by_name[name]
    entry = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(bench_dir, "traffic", wl["traffic"] + ".json")) as f:
        traffic = json.load(f)
    deployment = load_deployment(config.get(DEPLOYMENT_KEY, DEFAULT_DEPLOYMENT), bench_dir)
    return Cell(name=name, chips=int(wl["chips"]), config=config, traffic=traffic,
                deployment=deployment)


def metrics_of(bench: dict, cell: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    return [m for m in bench[kind] if cell in m.get("workloads", [cell])]


def _load(kind: str, name: str, bench_dir: str) -> ModuleType:
    """``<bench_dir>/<kind>/<name>.py``, loaded by path."""
    path = os.path.join(bench_dir, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"chip_{kind}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_reader(name: str, bench_dir: str = HERE):
    """``read(run) -> float | None`` of ``metrics/<name>.py``."""
    return _load("metrics", name, bench_dir).read


def load_deployment(name: str, bench_dir: str = HERE) -> ModuleType:
    """``deployments/<name>.py``: how a configuration's data is made, bound,
    planned, checked and counted.  It provides

      make_cohort(config, traffic, seed)   the cell's data, the same for the same seed
      bind(cohort, config)                 the ``repro.api.Study`` the scan runs on
      plan_kwargs(scan, spill_dir)         keywords of ``Study.plan``
      reference(cohort, config)            the plain reference ``compare.py`` holds
                                           the answers against
      control(cohort, config)              that reference one precision step down
      least_seconds(markers, samples, traits, peak)
                                           (least time of a cell's work on a chip with
                                           ``peak``, "compute" or "memory")

    A reference (and the control) answers ``r_pairs(markers, traits)``,
    ``r_block(markers, y)``, ``panel(traits)``, ``t(r)`` and ``nlp(t)``,
    where ``markers`` are genome marker indices as the scan reports them."""
    return _load("deployments", name, bench_dir)


# ------------------------------------------------------ spans and compiles


class Spans:
    """The benchmark's own host spans: kept here, and written into the
    profiler's trace as ``bench.<name>`` when a trace is on."""

    def __init__(self):
        self.rows: list[tuple[str, float, float]] = []

    @contextmanager
    def __call__(self, name: str):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
            try:
                yield
            finally:
                self.rows.append((name, t0, time.perf_counter()))

    def durations(self, name: str, lo: float = -math.inf, hi: float = math.inf) -> list[float]:
        return [e - s for n, s, e in self.rows if n == name and s >= lo and e <= hi]


class CompileLog:
    """jax.monitoring compile events (any thread): (event, time, seconds)."""

    def __init__(self):
        import jax

        self.rows: list[tuple[str, float, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event in COMPILE_EVENTS:
            self.rows.append((event, time.perf_counter(), duration))

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)


# --------------------------------------------------------------- one run


@dataclass
class Run:
    """What a metric reader reads.  Times are ``time.perf_counter()``."""

    cell: Cell
    spans: Spans
    compiles: CompileLog
    started: float                      # process start, as near as Python sees it
    window: tuple[float, float]         # first pull .. last cell written
    window_cells: list[tuple[int, int]] = field(default_factory=list)  # (markers, traits)
    scan_before: dict = field(default_factory=dict)   # ScanMetrics at window open
    scan_after: dict = field(default_factory=dict)    # ... and close
    trace: trace_reduce.Reduced | None = None
    trace_path: str | None = None       # the run's ``.xplane.pb`` (``trace_scopes.of``)
    peak: dict | None = None            # the chip's published peaks

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def tests(self) -> int:
        return sum(m * p for m, p in self.window_cells)


def scan_snapshot(metrics) -> dict:
    """The session's ``ScanMetrics`` at one moment.  ``counters`` is
    ``ScanMetrics.counters()`` (``refine_launches``, ``hits``, ... over live
    cells) and ``spans`` is ``span_totals()`` (``gwas.<name>`` without the
    prefix -> (seconds, count)), so a reader can take any of them as a
    window delta."""
    s = metrics.summary()
    return {"decode_s": metrics.decode_s_total, "extract_s": s["extract_s"],
            "markers": metrics.markers_done(), "cells": s["live_cells"],
            "h2d_bytes_per_marker": metrics.h2d_bytes_per_marker(),
            "counters": metrics.counters(), "spans": metrics.span_totals()}


def check_sample(seed: int, traffic: dict) -> dict:
    """The traits and the number of cells the check covers in full, from the seed."""
    rng = np.random.default_rng(seed_sequence(seed).spawn(1)[0])
    p = traffic["n_traits"]
    traits = np.sort(rng.choice(p, size=min(traffic["check_traits"], p), replace=False))
    return {"check_traits": traits, "check_cells": traffic["check_cells"], "rng": rng}


def _warm_up(events, writer, session, *, slots: int, per_slot: int) -> None:
    for _ in range(WARMUP_CELL_CAP):
        done = session.metrics.summary()["per_device"]
        if len(done) >= slots and all(d["cells"] >= per_slot for d in done.values()):
            return
        writer.write(next(events))
    raise RuntimeError(f"warm-up did not reach {per_slot} cells on each of {slots} "
                       f"slots within {WARMUP_CELL_CAP} cells")


def trace_file(trace_dir: str) -> str | None:
    """The ``.xplane.pb`` the profiler wrote under ``trace_dir``, if any."""
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    return found[0] if found else None


def reduce_trace(path: str, devices: list[int]):
    """(``trace_reduce``'s reduction, the result line's ``breakdown``) of the
    trace at ``path``: the device ops that took the most time, and the
    longest idle gaps labelled by the program's own spans
    (``trace_scopes.gap_labels``).  (None, None) where the trace holds no
    window or no device operation."""
    reduced = trace_reduce.reduce(trace_reduce.load(path), devices=devices)
    if reduced is None:
        return None, None
    gaps = trace_scopes.gap_labels(trace_scopes.load_once(path), devices)
    return reduced, {"device_ops": [list(x) for x in reduced.device_ops],
                     "idle_gaps": [list(x) for x in gaps]}


def _peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)


def run_cell(name: str, seed: int, seconds: float, trace: bool, *, started: float,
             root: str = ROOT, bench_dir: str = HERE, require_tpu: bool = True,
             compile_cache: bool = True, log=sys.stderr) -> dict:
    """One run of one cell; returns the result line's object."""
    import jax

    bench = load_benchmark(root)
    cell = find_cell(bench, name, root=root, bench_dir=bench_dir)
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and (dev.platform != "tpu" or len(devices) < cell.chips):
        raise NoChip(f"{name} needs {cell.chips} TPU chip(s); JAX found "
                     f"{len(devices)} {dev.platform} device(s) ({dev.device_kind}); "
                     "there is no CPU fallback")
    peak = work.peaks(dev.device_kind) if dev.platform == "tpu" else None
    if compile_cache:
        from repro.runtime.compile_cache import enable_compile_cache

        enable_compile_cache()
        # Every program goes to the cache, so runs after the first load all.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    work_dir = tempfile.mkdtemp(prefix="gwasbench_")
    try:
        return _run(cell, bench, seed, seconds, trace, started=started, work_dir=work_dir,
                    bench_dir=bench_dir, devices=devices, peak=peak, log=log)
    finally:
        shutil.rmtree(work_dir, ignore_errors=True)


def _run(cell, bench, seed, seconds, trace, *, started, work_dir, bench_dir, devices,
         peak, log) -> dict:
    import jax

    from repro.api import TsvWriter

    config, traffic, scan = cell.config, cell.traffic, cell.config["scan"]
    deployment = cell.deployment
    dev, used = devices[0], devices[:scan["devices"]]
    spans, compiles = Spans(), CompileLog()
    trace_dir = os.path.join(work_dir, "trace")
    events = writer = None
    try:
        with spans("setup.data"):
            cohort = deployment.make_cohort(config, traffic, seed)
        with spans("setup.bind"):
            study = deployment.bind(cohort, config)
        with spans("setup.prepare"):
            plan = study.plan(**deployment.plan_kwargs(scan, os.path.join(work_dir, "out")))
            plan.prepare()
        session = plan.run(resume=False)
        writer = TsvWriter(os.path.join(work_dir, "out"), spill_rows=scan["hit_spill_rows"])
        writer.open(session)
        events = session.events()
        with spans("setup.warmup"):
            _warm_up(events, writer, session, slots=len(used),
                     per_slot=config["warmup_cells_per_device"])

        before = scan_snapshot(session.metrics)
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.host_tracer_level = 1     # annotations, not the runtime's internals
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        answers, sizes = [], []
        t0 = time.perf_counter()
        with spans("window"):
            while time.perf_counter() - t0 < seconds:
                with spans("pull"):
                    got = next(events, None)
                if got is None:        # the genome's end: the rate is over the time taken
                    break
                with spans("write"):
                    writer.write(got)
                answers.append(compare.Answer.of(got))
                sizes.append((got.n_markers, got.n_traits))
        t1 = time.perf_counter()
        if trace:
            jax.profiler.stop_trace()
        after = scan_snapshot(session.metrics)
        memory_peak = _peak_bytes(used)
    finally:
        if events is not None:
            events.close()
        if writer is not None:
            writer.abort()
        compiles.close()
    executor = session.executor_info
    del events, writer, session, plan, study
    gc.collect()

    reduced = breakdown = None
    path = trace_file(trace_dir) if trace else None
    if path is not None:
        reduced, breakdown = reduce_trace(path, [d.id for d in used])

    run = Run(cell=cell, spans=spans, compiles=compiles, started=started, window=(t0, t1),
              window_cells=sizes, scan_before=before, scan_after=after, trace=reduced,
              trace_path=path, peak=peak)
    metrics = {}
    for entry in metrics_of(bench, cell.name, "per_layer" if trace else "end_to_end"):
        value = load_reader(entry["name"], bench_dir)(run)
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}

    t_ref = time.perf_counter()
    ref = deployment.reference(cohort, config)
    limits = config["limits"]
    numbers, failed = compare.compare(
        answers, ref, n_traits=traffic["n_traits"], batch_markers=scan["batch_markers"],
        n_markers=config["n_markers"], threshold=scan["hit_threshold_nlp"],
        limits=limits, **check_sample(seed, traffic))
    correct = compare.verdict(numbers, limits)
    phases = [("imports and device init", spans.rows[0][1] - started)]
    phases += [(n, e - s) for n, s, e in spans.rows if n.startswith("setup.")]
    phases += [("window", t1 - t0), ("reference check", time.perf_counter() - t_ref)]
    for n, s in phases:
        print(f"phase {n} {s:.3f} s", file=log)

    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
              "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": len(answers), "failed": failed,
              "metrics": metrics, "device": device}
    if trace and reduced is not None:
        device["busy_s"] = reduced.mean_busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = breakdown
    result["executor"] = {k: executor.get(k) for k in ("kind", "devices", "autotune")} \
        if executor else None
    for k in compare.ORDER:
        print(f"check {k} {numbers[k]!r} limit {limits[k]!r}", file=log, flush=True)
    result["checks"] = {k: {"value": numbers[k], "limit": limits[k]} for k in compare.ORDER}
    return result
