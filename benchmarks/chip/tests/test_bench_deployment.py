"""Deployment modules: a configuration that names its own module runs
through the harness with no edit to any file that is there, its reference
is asked about genome markers, and the default ``ols`` module reads
exactly what the benchmark read before deployments were modules."""
import hashlib
import json
import os
import time

import pytest

import bench_tiny
import control
import harness
import trace_reduce
import work

NOTING = '''"""A test deployment: the OLS one, with a reference that notes the genome
markers it is asked about and a work count that notes its calls."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "noting_ols", os.path.join(os.path.dirname(__file__), "ols.py"))
ols = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ols)
make_cohort, bind, plan_kwargs, control = ols.make_cohort, ols.bind, ols.plan_kwargs, ols.control
asked = {"r_pairs": [], "r_block": [], "least_seconds": 0}


class Noting(ols.Reference):
    def r_pairs(self, markers, traits):
        asked["r_pairs"].append(int(max(markers)))
        return super().r_pairs(markers, traits)

    def r_block(self, markers, y):
        asked["r_block"].append(int(max(markers)))
        return super().r_block(markers, y)


def reference(cohort, config):
    return Noting(cohort.pool, cohort.phenotypes, cohort.covariates, config["n_samples"])


def least_seconds(markers, samples, traits, peak):
    asked["least_seconds"] += 1
    return ols.least_seconds(markers, samples, traits, peak)
'''


def _files(top):
    return {os.path.join(d, f): open(os.path.join(d, f), "rb").read()
            for d, _, fs in os.walk(top) if "__pycache__" not in d for f in fs}


def test_a_configuration_runs_the_deployment_module_it_names(tmp_path, monkeypatch):
    root = str(tmp_path)
    bench_dir = bench_tiny.make_layout(root)
    before = _files(bench_tiny.BENCH)
    with open(os.path.join(bench_dir, "deployments", "noting.py"), "w") as f:
        f.write(NOTING)
    path = os.path.join(bench_dir, "tiny.json")
    with open(path) as f:
        config = json.load(f)
    config[harness.DEPLOYMENT_KEY] = "noting"
    with open(path, "w") as f:
        json.dump(config, f)
    loaded = {}
    real = harness.load_deployment

    def load(name, bench_dir=harness.HERE):
        loaded[name] = real(name, bench_dir)
        return loaded[name]

    monkeypatch.setattr(harness, "load_deployment", load)
    r = harness.run_cell(bench_tiny.CELL, 2**31 + 5, 600.0, False, started=time.perf_counter(),
                         root=root, bench_dir=bench_dir, require_tpu=False,
                         compile_cache=False)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    asked = loaded["noting"].asked
    pool = config["distinct_markers"]
    # genome markers, not pool rows: the genome recycles the pool four times,
    # and at most two of the four checked cells lie in its first pass
    assert max(asked["r_pairs"]) >= pool and max(asked["r_block"]) >= pool
    assert len(asked["r_block"]) == 4   # the tiny mix's check_cells, one block each

    # the roofline reader counts the work as the module does
    cell = harness.find_cell(harness.load_benchmark(root), bench_tiny.CELL, root=root,
                             bench_dir=bench_dir)
    peak = work.peaks("TPU v5 lite")
    run = harness.Run(cell=cell, spans=harness.Spans(), compiles=None, started=0.0,
                      window=(0.0, 1.0), window_cells=[(256, 64)] * 2, peak=peak,
                      trace=trace_reduce.Reduced(window_s=1.0, busy_s={0: 0.5}, device_ops=[]))
    value = harness.load_reader("assoc_roofline", bench_dir)(run)
    assert cell.deployment.asked["least_seconds"] == 2
    least = cell.deployment.ols.least_seconds(256, 512, 64, peak)[0]
    assert value == pytest.approx(100 * 2 * least / 0.5)
    assert _files(bench_tiny.BENCH) == before


def test_a_configuration_without_the_key_runs_ols(tmp_path):
    root = str(tmp_path)
    bench_dir = bench_tiny.make_layout(root)
    cell = harness.find_cell(harness.load_benchmark(root), bench_tiny.CELL, root=root,
                             bench_dir=bench_dir)
    assert harness.DEPLOYMENT_KEY not in cell.config
    assert cell.deployment.__file__ == os.path.join(bench_dir, "deployments", "ols.py")
    for bench_cell in harness.load_benchmark()["workloads"]:
        assert harness.find_cell(harness.load_benchmark(), bench_cell["name"]) \
            .deployment.__name__.endswith("_ols")


# ------------------------------------------ the ols module reads as it did

# Recorded before deployments were modules, on the tiny layout at this seed.
SEED = 2**31 + 101
COHORT_SHA256 = {
    "pool": "013d116bce226641c068ae0e96e15333c616277534ff3ea13eddb31bf5647ac4",
    "phenotypes": "458f1d0a7d7f7eb61b157e9ec6103adc2b62c95a8440a0bf9294a5ad1a5952d4",
    "covariates": "e41f1a49df9453a9b29c2fc1f6b6ab6bc0b2fdc067e16bc07319316db9911b01",
}
CHECKS = {"r_gap": 3.9648124716684663e-07, "nlp_gap": 2.6457602339891094e-05,
          "hits_missing": 0.0, "hits_spurious": 0.0, "best_wrong": 0.0, "cells_bad": 0.0}
CONTROL = {"r_gap": 1.643747960855535e-06, "nlp_gap": 0.005969774041136901,
           "hits_missing": 0.0, "hits_spurious": 0.0, "best_wrong": 1.0, "cells_bad": 0.0}


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("tiny"))
    bench_dir = bench_tiny.make_layout(root)
    return root, bench_dir, harness.find_cell(harness.load_benchmark(root), bench_tiny.CELL,
                                              root=root, bench_dir=bench_dir)


def test_the_ols_cohort_is_byte_for_byte_as_before(tiny):
    _, _, cell = tiny
    cohort = cell.deployment.make_cohort(cell.config, cell.traffic, SEED)
    got = {k: hashlib.sha256(getattr(cohort, k).tobytes()).hexdigest() for k in COHORT_SHA256}
    assert got == COHORT_SHA256


def test_the_ols_check_compares_the_same_numbers_as_before(tiny):
    root, bench_dir, _ = tiny
    r = harness.run_cell(bench_tiny.CELL, SEED, 600.0, False, started=time.perf_counter(),
                         root=root, bench_dir=bench_dir, require_tpu=False,
                         compile_cache=False)
    assert r["correct"] and r["attempted"] == 14        # the whole genome after warm-up
    assert {k: v["value"] for k, v in r["checks"].items()} == CHECKS


def test_the_ols_control_reads_the_same_numbers_as_before(tiny):
    _, _, cell = tiny
    numbers, failed, correct = control.reference_control(cell, 1, 4)
    assert numbers == CONTROL and failed == 4 and not correct
