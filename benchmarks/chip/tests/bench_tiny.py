"""A tiny copy of a benchmark layout for CPU tests: the real metric readers
and deployment modules, a configuration cut to a few hundred samples, and
one traffic mix."""
from __future__ import annotations

import json
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(os.path.dirname(BENCH))
for p in (BENCH, os.path.join(REPO, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

CELL = "tiny.t64"


def make_layout(root: str, *, engine: str = "dense") -> str:
    """Write ``root/BENCHMARK.json`` and a bench dir under ``root``; return it."""
    bench_dir = os.path.join(root, "bench")
    os.makedirs(os.path.join(bench_dir, "traffic"), exist_ok=True)
    for sub in ("metrics", "deployments"):
        shutil.copytree(os.path.join(BENCH, sub), os.path.join(bench_dir, sub),
                        dirs_exist_ok=True, ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(BENCH, "configs", "ukb23k_fused.json")) as f:
        config = json.load(f)
    config.update(n_samples=512, n_covariates=3, n_markers=4096, distinct_markers=1024)
    config["scan"].update(engine=engine, batch_markers=256)
    with open(os.path.join(bench_dir, "tiny.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(bench_dir, "traffic", "t64.json"), "w") as f:
        json.dump({"n_traits": 64, "hit_density": 1e-4, "effect_r2": 0.2,
                   "covariate_loading_sd": 0.5, "check_traits": 16,
                   "check_cells": 4}, f)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [dict(bench["configs"][0], name="tiny", file="bench/tiny.json")]
    bench["workloads"] = [{"name": CELL, "config": "tiny", "traffic": "t64", "chips": 1,
                           "why": "CPU test"}]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return bench_dir
