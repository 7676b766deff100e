"""The check that decides ``correct``, driven through a whole run at a tiny
size on the CPU: a sound run passes, and each fault planted under the
timed path, and the control, come out not correct."""
import time

import numpy as np
import pytest

import bench_tiny
import control
import harness


@pytest.fixture(scope="module")
def layout(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("tiny"))
    return root, bench_tiny.make_layout(root)


def _run(layout, seed=2**31 + 11, trace=False):
    root, bench_dir = layout
    return harness.run_cell(bench_tiny.CELL, seed, 30.0, trace, started=time.perf_counter(),
                            root=root, bench_dir=bench_dir, require_tpu=False,
                            compile_cache=False)


def test_a_sound_run_is_correct_and_reports_its_metrics(layout):
    r = _run(layout)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"tests_per_s", "setup_s"}
    assert list(r)[-1] == "checks"
    assert r["checks"]["r_gap"]["value"] > 0        # hit rows were compared
    assert r["device"]["platform"] == "cpu"


def test_a_traced_run_reports_per_layer_metrics(layout):
    r = _run(layout, seed=5, trace=True)
    assert r["correct"]
    m = r["metrics"]
    assert m["compiles_in_window"]["value"] == 0
    assert m["h2d_bytes_per_marker"]["value"] == 128     # packed N/4 bytes, dense engine
    for name in ("prepare_s", "compile_s", "host_decode_ms_per_batch",
                 "extract_ms_per_cell", "write_ms_per_cell"):
        assert m[name]["value"] > 0, name
    # no TPU plane on the CPU: the device metrics have nothing to read
    assert "device_idle_share" not in m and "assoc_roofline" not in m


def _half_batch(monkeypatch):
    from repro.core.engines import DenseEngine

    real = DenseEngine.prepare_batch

    def prepare_batch(self, source, batch, ctx):
        hb = real(self, source, batch, ctx)
        slab = np.array(hb.device_args[0])
        slab[len(slab) // 2:] = 0x55            # every call missing: the half left out
        hb.device_args = (slab,)
        return hb

    monkeypatch.setattr(DenseEngine, "prepare_batch", prepare_batch)


def _hit_moved(monkeypatch):
    import repro.api.session as session

    real = session.extract_hits

    def extract_hits(view, threshold):
        hits, stats = real(view, threshold)
        hits = hits.copy()
        hits[:1, 0] += 1                        # first hit row names its neighbour marker
        return hits, stats

    monkeypatch.setattr(session, "extract_hits", extract_hits)


def _nlp_altered(monkeypatch):
    import repro.core.sinks as sinks

    real = sinks._stats.refine_neglog10p
    monkeypatch.setattr(sinks._stats, "refine_neglog10p",
                        lambda t, dof, **kw: real(t, dof, **kw) * np.float32(1.01))


@pytest.mark.parametrize("fault", [_half_batch, _hit_moved, _nlp_altered])
def test_a_fault_under_the_timed_path_is_not_correct(layout, monkeypatch, fault):
    fault(monkeypatch)
    r = _run(layout, seed=77)
    assert not r["correct"] and r["failed"] > 0


def test_the_control_is_not_correct(layout, monkeypatch):
    root, bench_dir = layout
    cell = harness.find_cell(harness.load_benchmark(root), bench_tiny.CELL, root=root,
                             bench_dir=bench_dir)
    made = []
    real = cell.deployment.control
    monkeypatch.setattr(cell.deployment, "control",
                        lambda cohort, config: made.append(real(cohort, config)) or made[-1])
    for seed in (1, 2, 3):
        numbers, failed, correct = control.reference_control(cell, seed, 4)
        assert not correct and failed > 0
        assert numbers["nlp_gap"] > cell.config["limits"]["nlp_gap"]
    assert len(made) == 3       # the control is the configuration's deployment's
