"""A configuration, a traffic mix and a per-layer metric added as files are
found by the harness; no existing file is edited."""
import json
import os

import pytest

import bench_tiny
import harness


def test_new_files_are_found_by_name(tmp_path):
    root = str(tmp_path)
    bench_dir = bench_tiny.make_layout(root)
    before = {p: open(p, "rb").read() for p in _files(bench_tiny.BENCH)}

    # A later PR: one more configuration, traffic mix and metric, as files.
    with open(os.path.join(bench_dir, "tiny.json")) as f:
        config = json.load(f)
    config["n_samples"] = 256
    with open(os.path.join(bench_dir, "tiny256.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(bench_dir, "traffic", "t8.json"), "w") as f:
        json.dump({"n_traits": 8, "hit_density": 1e-4, "effect_r2": 0.2,
                   "covariate_loading_sd": 0.5, "check_traits": 4, "check_cells": 2}, f)
    with open(os.path.join(bench_dir, "metrics", "cells_in_window.py"), "w") as f:
        f.write("def read(run):\n    return float(len(run.window_cells))\n")
    bench = harness.load_benchmark(root)
    bench["configs"].append(dict(bench["configs"][0], name="tiny256", file="bench/tiny256.json"))
    bench["workloads"].append({"name": "tiny256.t8", "config": "tiny256", "traffic": "t8",
                               "chips": 1, "why": "added as files"})
    bench["per_layer"].append({"name": "cells_in_window", "unit": "count", "better": "higher",
                               "source": "host_clock", "layer": "writers",
                               "moves": "tests_per_s", "workloads": ["tiny256.t8"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    bench = harness.load_benchmark(root)
    cell = harness.find_cell(bench, "tiny256.t8", root=root, bench_dir=bench_dir)
    assert cell.config["n_samples"] == 256 and cell.traffic["n_traits"] == 8
    names = [m["name"] for m in harness.metrics_of(bench, "tiny256.t8", "per_layer")]
    assert "cells_in_window" in names and "device_idle_share" in names
    assert "cells_in_window" not in [
        m["name"] for m in harness.metrics_of(bench, bench_tiny.CELL, "per_layer")]
    read = harness.load_reader("cells_in_window", bench_dir)
    run = harness.Run(cell=cell, spans=harness.Spans(), compiles=None, started=0.0,
                      window=(1.0, 2.0), window_cells=[(4, 8)] * 3)
    assert read(run) == 3.0
    assert {p: open(p, "rb").read() for p in _files(bench_tiny.BENCH)} == before


def test_every_metric_in_the_benchmark_has_a_reader():
    bench = harness.load_benchmark()
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(harness.load_reader(m["name"]))
    for w in bench["workloads"]:
        cell = harness.find_cell(bench, w["name"])
        assert cell.chips == cell.config["scan"]["devices"]


def test_an_unknown_workload_is_an_error():
    with pytest.raises(KeyError, match="no workload"):
        harness.find_cell(harness.load_benchmark(), "nope.p1")


def _files(top):
    return sorted(os.path.join(d, f) for d, _, fs in os.walk(top) for f in fs
                  if "__pycache__" not in d)
