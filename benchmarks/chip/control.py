#!/usr/bin/env python3
"""The correctness check's control, run on the chip at a cell's own size.

    python3 benchmarks/chip/control.py --workload ukb23k_fused.p20480 --seeds 1 2 3
    python3 benchmarks/chip/control.py --workload ukb23k_fused.p20480 --seeds 1 2 3 \
        --program bf16

Without ``--program``, the reference one precision step down (the
``control(cohort, config)`` of the configuration's deployment module) is
put in the program's place: it answers
the cells a window would hold, one per distinct stretch of the pool, and
those answers go through the same comparison as a run's.  With
``--program bf16`` the program itself runs the cell with its own bf16 GEMM
path switched on (``input_dtype="bf16"`` for the fused engine,
``precision="bf16"`` for the dense one).  Either way every seed must come
out not correct; the numbers read here set the upper end of each limit
(``PERF.md``).  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import tempfile
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def control_answers(low, cells: list[tuple[int, int]], n_traits: int, threshold: float):
    """What the lower-precision reference answers for each (lo, hi) cell."""
    from compare import Answer, t_at

    y = low.panel(np.arange(n_traits))
    screen = t_at(low, threshold) * 0.98
    cols = np.arange(n_traits)
    out = []
    for lo, hi in cells:
        r = low.r_block(lo + np.arange(hi - lo), y)
        t = low.t(r)
        best_row = np.argmax(t * t, axis=0)
        best_nlp = low.nlp(t[best_row, cols])
        m, j = np.nonzero(np.abs(t) >= screen)
        nlp = low.nlp(t[m, j])
        keep = nlp >= threshold
        hits = np.stack([lo + m[keep], j[keep]], 1)
        stats = np.stack([r[m, j][keep], t[m, j][keep], nlp[keep]], 1)
        out.append(Answer(lo, hi, 0, n_traits, hits, stats, best_nlp, best_row))
    return out


def reference_control(cell, seed: int, n_cells: int):
    """(numbers, failed cells, correct) of the lower-precision reference."""
    import compare
    from harness import check_sample

    config, traffic, scan = cell.config, cell.traffic, cell.config["scan"]
    deployment = cell.deployment
    cohort = deployment.make_cohort(config, traffic, seed)
    b = scan["batch_markers"]
    first = config["warmup_cells_per_device"]
    cells = [(k * b, (k + 1) * b) for k in range(first, first + n_cells)]
    answers = control_answers(deployment.control(cohort, config), cells, traffic["n_traits"],
                              scan["hit_threshold_nlp"])
    limits = config["limits"]
    numbers, failed = compare.compare(
        answers, deployment.reference(cohort, config), n_traits=traffic["n_traits"],
        batch_markers=b, n_markers=config["n_markers"], threshold=scan["hit_threshold_nlp"],
        limits=limits, **check_sample(seed, traffic))
    return numbers, failed, compare.verdict(numbers, limits)


def program_control(name: str, seed: int, seconds: float, *, root: str = ROOT,
                    bench_dir: str = HERE, started: float, **run_kwargs):
    """The program's own bf16 path, run through the harness on a copy of
    the cell's configuration with that path switched on."""
    import harness

    bench = harness.load_benchmark(root)
    cell = harness.find_cell(bench, name, root=root, bench_dir=bench_dir)
    config = copy.deepcopy(cell.config)
    key = "input_dtype" if config["scan"]["engine"] == "fused" else "precision"
    config["scan"][key] = "bf16"
    with tempfile.TemporaryDirectory(prefix="gwasbench_control_") as tmp:
        path = os.path.join(tmp, "config.json")
        with open(path, "w") as f:
            json.dump(config, f)
        wl = next(w for w in bench["workloads"] if w["name"] == name)
        for c in bench["configs"]:
            if c["name"] == wl["config"]:
                c["file"] = path
        with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
            json.dump(bench, f)
        return harness.run_cell(name, seed, seconds, False, started=started, root=tmp,
                                bench_dir=bench_dir, **run_kwargs)


def main(argv=None) -> int:
    started = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--cells", type=int, default=8,
                    help="cells the reference control answers per seed")
    ap.add_argument("--program", choices=("bf16",), default=None)
    ap.add_argument("--seconds", type=float, default=5.0,
                    help="window of a --program run")
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), HERE]
    import harness

    cell = harness.find_cell(harness.load_benchmark(), args.workload)
    for seed in args.seeds:
        t = time.perf_counter()
        if args.program:
            r = program_control(args.workload, seed, args.seconds, started=started)
            numbers = {k: v["value"] for k, v in r["checks"].items()}
            correct = r["correct"]
        else:
            numbers, _, correct = reference_control(cell, seed, args.cells)
        print(json.dumps({"seed": seed, "control": args.program or "reference",
                          "correct": correct, "numbers": numbers,
                          "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
