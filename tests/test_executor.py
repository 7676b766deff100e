"""The multi-device grid executor (DESIGN.md §12).

The contract under test: the (marker-batch x trait-block) grid drained by
N devices through the work-stealing ``CellScheduler`` produces *bitwise*
the outputs of the serial single-device walk — for dense, fused, and lmm
(incl. LOCO) engines, under both placement policies, and across resumes
whose device count differs from the run that wrote the checkpoint.  Real
multi-device semantics run on 8 fake CPU devices in a subprocess (the
parent must keep seeing one device); the scheduler, executor machinery,
spec plumbing, and metrics are covered in-process.
"""
import json
import os
import subprocess
import sys
import textwrap
import threading

import numpy as np
import pytest

from repro.api import ExecSpec, GridSpec, Study, TsvWriter
from repro.api.session import MultiDeviceExecutor, SerialExecutor
from repro.io import plink
from repro.runtime.prefetch import MarkerBatch, TraitBlock
from repro.runtime.scheduler import CellRun, CellScheduler


@pytest.fixture(scope="module")
def source(cohort_files):
    return plink.PlinkBed(cohort_files["bed"])


@pytest.fixture(scope="module")
def study(source, cohort):
    return Study.from_arrays(source, cohort.phenotypes, cohort.covariates)


def _grid(**kw):
    base = dict(batch_markers=128, block_m=64, block_n=128, block_p=4)
    base.update(kw)
    return GridSpec(**base)


def _batches(n, size=10):
    return [
        MarkerBatch(index=i, lo=i * size, hi=(i + 1) * size, source_id=0,
                    local_lo=i * size, local_hi=(i + 1) * size)
        for i in range(n)
    ]


def _blocks(n, width=4):
    return [TraitBlock(index=k, lo=k * width, hi=(k + 1) * width) for k in range(n)]


# ---------------------------------------------------------------- scheduler


def test_scheduler_marker_major_items_sweep_blocks():
    sched = CellScheduler(_batches(3), _blocks(2), placement="marker-major")
    assert sched.n_items == 3 and sched.n_cells == 6
    for run in sched.items:
        assert [k.index for k in run.blocks] == [0, 1]
    assert [run.batch.index for run in sched.items] == [0, 1, 2]


def test_scheduler_trait_major_items_are_block_major_cells():
    sched = CellScheduler(_batches(3), _blocks(2), placement="trait-major")
    assert sched.n_items == 6 and sched.n_cells == 6
    # block-major enumeration: a contiguous lease stays in one panel column
    assert [(r.batch.index, r.blocks[0].index) for r in sched.items] == [
        (0, 0), (1, 0), (2, 0), (0, 1), (1, 1), (2, 1)
    ]


def test_scheduler_pending_filter_mid_panel():
    pending = {(0, 1), (2, 0), (2, 1)}   # batch 0 half done, batch 1 done
    sched = CellScheduler(_batches(3), _blocks(2), pending)
    assert [(r.batch.index, [k.index for k in r.blocks]) for r in sched.items] == [
        (0, [1]), (2, [0, 1])
    ]
    assert sched.n_cells == 3


def test_scheduler_lease_capped_to_spread_over_workers():
    """Short scans must still use every slot: the lease is capped at
    n_items / n_workers, otherwise the first claims would take everything
    and leave only unstealable <=1-item leases behind."""
    sched = CellScheduler(_batches(6), _blocks(3), lease_size=2, n_workers=4)
    assert sched.lease_size == 1
    assert all(sched.claim(f"w{i}") is not None for i in range(4))
    # plenty of items: the cap does not bind
    assert CellScheduler(_batches(24), _blocks(1), lease_size=2, n_workers=4).lease_size == 2
    # no worker count given (tests, single-slot callers): untouched
    assert CellScheduler(_batches(6), _blocks(1), lease_size=4).lease_size == 4


def test_scheduler_rejects_unknown_placement():
    with pytest.raises(ValueError, match="placement"):
        CellScheduler(_batches(1), _blocks(1), placement="diagonal")


def test_scheduler_drains_under_contention():
    sched = CellScheduler(_batches(24), _blocks(3), lease_size=4)
    seen, lock = [], threading.Lock()

    def drain(worker):
        while True:
            claim = sched.claim(worker)
            if claim is None:
                return
            idx, run = claim
            with lock:
                seen.extend((run.batch.index, k.index) for k in run.blocks)
            sched.complete(worker, idx)

    threads = [threading.Thread(target=drain, args=(f"w{i}",)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert sorted(seen) == sorted((b, k) for b in range(24) for k in range(3))
    assert len(seen) == len(set(seen))  # items never claimed twice
    assert sched.remaining() == 0


# ------------------------------------------------------------------- specs


def test_exec_spec_validation(study):
    with pytest.raises(ValueError, match="devices"):
        ExecSpec(devices=-1).validate()
    with pytest.raises(ValueError, match="placement"):
        study.plan(executor=ExecSpec(placement="diag"))
    with pytest.raises(ValueError, match="lease_batches"):
        study.plan(executor=ExecSpec(lease_batches=0))


def test_exec_spec_roundtrip_and_fingerprint_free(study):
    from repro.api.specs import ScanConfig

    cfg = ScanConfig.from_specs(
        executor=ExecSpec(devices=4, placement="trait-major", lease_batches=3)
    )
    assert cfg.exec_spec() == ExecSpec(4, "trait-major", 3)
    # executor shape never enters the checkpoint identity: a scan cut under
    # one device count must resume under any other
    assert cfg.fingerprint_payload() == ScanConfig().fingerprint_payload()


def test_more_devices_than_visible_rejected(study):
    session = study.plan(grid=_grid(), executor=ExecSpec(devices=97)).run()
    with pytest.raises(ValueError, match="devices=97"):
        next(session.events())


def test_custom_step_rejected_under_multi_device(study):
    """The shim's swappable ``_step`` hook carries a single prolog memo —
    it cannot ride N worker threads, and silently dropping it would lose
    the caller's patched math; refuse loudly."""
    from repro.api.session import ScanSession

    prep = study.plan(grid=_grid(), executor=ExecSpec(devices=2)).prepare()
    session = ScanSession(prep, step=lambda *a: {})
    with pytest.raises(ValueError, match="custom step"):
        next(session.events())


def test_mesh_and_multi_device_exclusive(study):
    import dataclasses

    import jax
    from jax.sharding import Mesh

    from repro.api.session import ScanSession

    prep = study.plan(grid=_grid(), executor=ExecSpec(devices=2)).prepare()
    meshed = dataclasses.replace(prep, mesh=Mesh(np.array(jax.devices()[:1]), ("model",)))
    with pytest.raises(ValueError, match="exclusive"):
        ScanSession(meshed)


# ----------------------------------------- executor machinery (one device)


def _collect(executor, todo, pending=None):
    out = {}
    for cell, timing in executor.cells(todo, pending):
        out[(cell.batch_index, cell.block_index)] = cell
        assert timing.wall_s >= 0 and timing.n_markers == cell.n_markers
    return out


def test_multi_executor_machinery_matches_serial(study):
    """The worker/queue/scheduler machinery with a single slot must produce
    exactly the serial walk's cells (same set, same arrays bitwise) — the
    device count then only changes who computes, which the 8-fake-device
    subprocess asserts."""
    plan = study.plan(grid=_grid(trait_block=4), hit_threshold_nlp=2.0)
    prep = plan.prepare()
    ref = _collect(SerialExecutor(prep), prep.batches)
    for placement in ("marker-major", "trait-major"):
        got = _collect(
            MultiDeviceExecutor(prep, n_devices=1, placement=placement),
            prep.batches,
        )
        assert set(got) == set(ref)
        for key, cell in got.items():
            for k, v in ref[key].arrays.items():
                np.testing.assert_array_equal(v, cell.arrays[k], err_msg=f"{key}:{k}")


_PIPELINE_THREADS = (
    "scan-device", "slot-decode", "slot-tail", "panel-prefetch-dev"
)


def _leaked_pipeline_threads():
    import time as _time

    # teardown joins everything before the generator's close() returns;
    # the brief poll only absorbs scheduler jitter on loaded CI boxes
    for _ in range(50):
        alive = [
            t for t in threading.enumerate()
            if t.name.startswith(_PIPELINE_THREADS) and t.is_alive()
        ]
        if not alive:
            return []
        _time.sleep(0.02)
    return alive


def test_multi_executor_propagates_worker_errors(study):
    plan = study.plan(grid=_grid(trait_block=4))
    prep = plan.prepare()
    ex = MultiDeviceExecutor(prep, n_devices=1)
    boom_calls = {"n": 0}

    real_prepare = prep.engine.prepare_batch

    def exploding(source, batch, ctx):
        boom_calls["n"] += 1
        if boom_calls["n"] > 1:
            raise RuntimeError("decode exploded")
        return real_prepare(source, batch, ctx)

    prep.engine.prepare_batch = exploding
    try:
        with pytest.raises(RuntimeError, match="decode exploded"):
            list(ex.cells(prep.batches, None))
    finally:
        prep.engine.prepare_batch = real_prepare
    assert not _leaked_pipeline_threads()


def test_multi_executor_early_close_joins_workers(study):
    plan = study.plan(grid=_grid(trait_block=4))
    prep = plan.prepare()
    gen = MultiDeviceExecutor(prep, n_devices=1).cells(prep.batches, None)
    next(gen)
    gen.close()
    assert not _leaked_pipeline_threads()


def test_pipelined_teardown_releases_slots_mid_stream(study, monkeypatch):
    """Closing ``events()`` mid-scan tears down the whole per-slot
    pipeline: decode pool, tail, and panel look-ahead threads are joined,
    and every slot is reset (dropping its staged device panel blocks and
    engine arrays — nothing stays pinned on the devices)."""
    import repro.api.session as session_mod

    plan = study.plan(grid=_grid(trait_block=4))
    prep = plan.prepare()
    resets = []
    real_reset = session_mod._Slot.reset

    def spy(self):
        resets.append(self.label)
        return real_reset(self)

    monkeypatch.setattr(session_mod._Slot, "reset", spy)
    ex = MultiDeviceExecutor(prep, n_devices=1, slot_prefetch=2)
    gen = ex.cells(prep.batches, None)
    next(gen)
    gen.close()
    assert resets  # every worker's finally ran its slot teardown
    assert not _leaked_pipeline_threads()


def test_panel_view_release_drops_staged_blocks(study):
    """The slot-teardown primitive: release() empties the per-device LRU
    (no pinned panel buffers survive the scan) but the view restages on
    demand with identical bytes."""
    import jax

    prep = study.plan(grid=_grid(trait_block=4)).prepare()
    view = prep.panels.device_view(jax.devices()[0])
    blk = prep.trait_blocks[0]
    before = np.asarray(view.device_block(blk))
    assert len(view._dev) == 1
    view.release()
    assert len(view._dev) == 0
    np.testing.assert_array_equal(np.asarray(view.device_block(blk)), before)


# ----------------------------------------------------------------- metrics


def test_session_metrics_recorded(study):
    session = study.plan(grid=_grid(trait_block=4)).run()
    seen = []
    session.progress = lambda m: seen.append(m.cells_done)
    cells = list(session.events())
    m = session.metrics
    assert m.cells_done == len(cells) == session.n_batches * session.n_trait_blocks
    assert seen == list(range(1, len(cells) + 1))
    s = m.summary()
    assert s["cells"] == s["live_cells"] == len(cells)
    assert s["replayed_cells"] == 0
    assert s["markers_per_s"] > 0 and s["trait_markers_per_s"] > 0
    assert s["wall_s"] > 0
    assert set(s["per_device"]) == {"serial"}
    assert s["per_device"]["serial"]["cells"] == len(cells)
    assert m.markers_done() == session.n_markers
    assert m.trait_markers_done() == session.n_markers * session.n_traits
    assert "cells" in m.progress_line()
    assert session.executor_info == {"kind": "serial", "devices": 1}


def test_session_metrics_separate_replayed_cells(study, tmp_path):
    ck = str(tmp_path / "ck")
    kw = dict(grid=_grid(trait_block=4), checkpoint_dir=ck)
    list(study.plan(**kw).run().events())
    session = study.plan(**kw).run()
    cells = list(session.events())
    assert all(c.replayed for c in cells)
    s = session.metrics.summary()
    assert s["live_cells"] == 0 and s["replayed_cells"] == len(cells)
    assert s["markers_per_s"] == 0.0   # replay costs np.load, not a device step


# ----------------------------------------------- out-of-order cell folding
#
# The executor's correctness spine: any completion order produces the same
# outputs.  The hypothesis property (tests/test_property.py) explores the
# space; these fixed cases run in environments without hypothesis and pin
# the tie-break rule the normalization exists for.


def test_best_trait_fold_is_completion_order_invariant():
    """Exact best-nlp ties across batches resolve to the LOWER global
    marker no matter which cell folds first — the serial result, made
    order-free."""
    from repro.core.sinks import BestTraitSink

    a = (np.asarray([2.5, 0.0, 3.0], np.float32), np.asarray([1, 0, 2], np.int32), 0)
    b = (np.asarray([2.5, 0.0, 1.0], np.float32), np.asarray([4, 0, 0], np.int32), 100)
    for order in ([a, b], [b, a]):
        sink = BestTraitSink(3)
        for best, row, lo in order:
            sink._fold(best, row, lo, 0)
        np.testing.assert_array_equal(sink.best_nlp, [2.5, 0.0, 3.0])
        # trait 0 ties at 2.5: marker 1 beats marker 104 in either order;
        # trait 1 never fires (stays -1); trait 2 is a plain max
        np.testing.assert_array_equal(sink.best_marker, [1, -1, 2])


def test_session_cells_fold_identically_in_any_order(study, source, cohort, tmp_path):
    """Replaying one session's committed cells through writers in shuffled
    orders produces byte-identical outputs (the multi-device completion
    order is one such shuffle)."""
    from repro.api.session import CheckpointReplay

    ck = str(tmp_path / "ck")
    session = study.plan(
        grid=_grid(trait_block=4), hit_threshold_nlp=1.0, checkpoint_dir=ck
    ).run()
    ref_dir = tmp_path / "ref"
    session.stream_to(TsvWriter(str(ref_dir)))
    files = ("hits.tsv", "per_trait_best.tsv", "qc.tsv")
    ref = {f: (ref_dir / f).read_text() for f in files}

    replay = CheckpointReplay(
        ck, marker_ids=source.marker_ids, trait_names=study.trait_names
    )
    cells = list(replay.events())
    rng = np.random.default_rng(0)
    for trial in range(3):
        order = rng.permutation(len(cells))
        out = tmp_path / f"perm{trial}"
        w = TsvWriter(str(out))
        w.open(replay)
        for i in order:
            w.write(cells[i])
        w.close()
        assert {f: (out / f).read_text() for f in files} == ref


# ------------------------------- multi-device semantics (8 fake devices)


_CHILD = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, tempfile
    import os.path as osp
    from repro.api import ExecSpec, GridSpec, LmmSpec, Study, TsvWriter
    from repro.core.association import AssocOptions
    from repro.io import open_genotypes, synth

    co = synth.make_cohort(n_samples=200, n_markers=400, n_traits=12,
                           n_causal=4, seed=5)
    d = tempfile.mkdtemp()
    beds = synth.write_split_plink(co, osp.join(d, "toy"), n_shards=3)
    src = open_genotypes(",".join(beds))
    study = Study.from_arrays(src, co.phenotypes, co.covariates)
    grid = GridSpec(batch_markers=128, block_m=64, block_n=128, block_p=4,
                    trait_block=4)
    FILES = ("hits.tsv", "per_trait_best.tsv", "qc.tsv")

    def read(out):
        return {f: open(osp.join(out, f)).read() for f in FILES}

    def scan(tag, *, executor=None, checkpoint_dir=None, **plan_kw):
        session = study.plan(
            grid=grid, hit_threshold_nlp=2.0, executor=executor,
            checkpoint_dir=checkpoint_dir, **plan_kw,
        ).run()
        out = osp.join(d, tag)
        session.stream_to(TsvWriter(out))
        return read(out), session

    out = {}
    cases = {
        "dense": {},
        "dense_exact": {"options": AssocOptions(dof_mode="exact")},
        "fused": {"engine": "fused"},
        "lmm_loco": {"engine": "lmm", "lmm": LmmSpec(loco=True)},
    }
    for name, kw in cases.items():
        ref, _ = scan(f"{name}_serial", **kw)
        multi, session = scan(
            f"{name}_md",
            executor=ExecSpec(devices=3 if name != "fused" else 8), **kw,
        )
        out[f"{name}_identical"] = multi == ref
        info = session.executor_info
        out[f"{name}_workers"] = len(info["workers"])
        out[f"{name}_slot_devices"] = info["slot_devices"]
        out[f"{name}_devices_used"] = len(
            session.metrics.summary()["per_device"]
        )
        if name == "dense":
            tm, _ = scan(f"{name}_tm", executor=ExecSpec(
                devices=4, placement="trait-major", lease_batches=1), **kw)
            out["dense_trait_major_identical"] = tm == ref
            stolen = sum(w["stolen_by"] for w in info["workers"].values())
            out["dense_steals"] = stolen  # informational; may be 0
            # forced-unpipelined worker (slot_prefetch=0, autotune off) is
            # the same bytes as both the serial walk and the pipelined run
            unp, _ = scan(f"{name}_unpiped", executor=ExecSpec(
                devices=3, slot_prefetch=0, autotune_lease=False), **kw)
            out["dense_unpipelined_identical"] = unp == ref
            out["dense_autotune"] = info["autotune"]
            out["dense_slot_prefetch"] = info["slot_prefetch"]
            md = session.metrics.summary()
            out["dense_per_device_decode"] = all(
                "decode_s" in v and "stage_s" in v
                for v in md["per_device"].values()
            )
            out["dense_decode_total"] = md["decode_s"]

    # Resume with a DIFFERENT device count: full 2-device checkpointed run,
    # cut one whole batch plus a mid-panel cell, resume on 4 devices.
    ck = osp.join(d, "ck")
    full, _ = scan("resume_full", executor=ExecSpec(devices=2),
                   checkpoint_dir=ck)
    mpath = osp.join(ck, "manifest.json")
    mani = json.load(open(mpath))
    lost = [k for k in mani["completed"] if k.startswith("1.")] + ["2.1"]
    for k in lost:
        mani["completed"].pop(k)
    json.dump(mani, open(mpath, "w"))
    resumed, session = scan(
        "resume_md",
        executor=ExecSpec(devices=4, placement="trait-major"),
        checkpoint_dir=ck,
    )
    out["resume_identical"] = resumed == full
    m = session.metrics.summary()
    out["resume_replayed"] = m["replayed_cells"]
    out["resume_live"] = m["live_cells"]
    out["resume_cells_total"] = session.n_batches * session.n_trait_blocks

    print(json.dumps(out))
    """
)


@pytest.fixture(scope="module")
def child_results(tmp_path_factory):
    src = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"
    )
    env = dict(os.environ, PYTHONPATH=src)
    # Scratch cwd under the test tmp tree: a child's relative writes must
    # never land in the repo checkout (see the conftest guard).
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD], capture_output=True, text=True,
        timeout=900, env=env, cwd=str(tmp_path_factory.mktemp("exec_child")),
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("engine", ["dense", "dense_exact", "fused", "lmm_loco"])
def test_multi_device_bitwise_identical(child_results, engine):
    assert child_results[f"{engine}_identical"] is True
    assert child_results[f"{engine}_workers"] >= 2
    assert child_results[f"{engine}_devices_used"] >= 2


@pytest.mark.parametrize("engine", ["dense", "dense_exact", "fused", "lmm_loco"])
def test_slot_outputs_live_on_their_own_device(child_results, engine):
    """Every slot that computed a cell holds its step outputs on exactly one
    device, and no two slots share one: placement is explicit per slot."""
    placed = child_results[f"{engine}_slot_devices"]
    assert len(placed) >= 2
    assert all(len(devs) == 1 for devs in placed.values())
    assert len({devs[0] for devs in placed.values()}) == len(placed)


def test_trait_major_placement_bitwise_identical(child_results):
    assert child_results["dense_trait_major_identical"] is True


def test_unpipelined_fallback_bitwise_identical(child_results):
    """--slot-prefetch 0 (the historical one-staged-batch worker) and the
    pipelined default produce the same bytes — pipelining only moves WHEN
    host work happens, never what is computed."""
    assert child_results["dense_unpipelined_identical"] is True


def test_autotune_and_pipeline_reported(child_results):
    at = child_results["dense_autotune"]
    assert at["enabled"] is True
    assert at["initial_lease"] >= 1 and at["final_lease"] >= 1
    assert at["final_lease"] <= at["initial_lease"]  # tuner only shrinks
    assert at["adjustments"] >= 0
    assert child_results["dense_slot_prefetch"] == 1
    # decode/stage time is attributed per device in the metrics summary
    assert child_results["dense_per_device_decode"] is True
    assert child_results["dense_decode_total"] > 0


def test_resume_across_device_counts(child_results):
    assert child_results["resume_identical"] is True
    # the cut lost one whole batch (all its blocks) plus one mid-panel
    # cell: some cells replay, some recompute, every cell exactly once
    assert child_results["resume_replayed"] > 0
    assert child_results["resume_live"] > 0
    assert (
        child_results["resume_replayed"] + child_results["resume_live"]
        == child_results["resume_cells_total"]
    )
