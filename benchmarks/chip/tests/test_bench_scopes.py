"""The program's names in a trace: per-scope device time as a union, gap
labels from the innermost program span, and the five readers of them."""
import os

import pytest

import bench_tiny  # noqa: F401
import harness
import trace_reduce as tr
import trace_scopes as ts

# One chip's window, 0-20 us.  Device ops (us): pad 0-2 and the kernel 2-6
# under gwas.assoc; a conditional 6-9 with a body op 6.5-8.5 nested in it
# under gwas.epilogue.compact; an unscoped copy 9.5-10; a refine op 12-13
# (no op_name, as XLA leaves the refine's loop: its module run names it);
# a device decode op 14-15.  Host: the window's thread (line 1) and a
# decode worker (line 2), whose span must not label the chip's gaps.  A
# second chip (TPU:1) runs slot dev1, whose spans sit on line 3.
XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 4000000 }
    events { metadata_id: 3 offset_ps: 6000000 duration_ps: 3000000 }
    events { metadata_id: 4 offset_ps: 6500000 duration_ps: 2000000 }
    events { metadata_id: 5 offset_ps: 9500000 duration_ps: 500000 }
    events { metadata_id: 6 offset_ps: 12000000 duration_ps: 1000000 }
    events { metadata_id: 7 offset_ps: 14000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 8 offset_ps: 0 duration_ps: 9000000 }
    events { metadata_id: 9 offset_ps: 11900000 duration_ps: 1200000 } }
  event_metadata { key: 1 value { id: 1 name: "pad.0"
    stats { metadata_id: 100 str_value: "jit(gwas_fused_step)/gwas.assoc/pad" } } }
  event_metadata { key: 2 value { id: 2 name: "gwas_dot"
    stats { metadata_id: 100 str_value: "jit(gwas_fused_step)/gwas.assoc/pallas_call" } } }
  event_metadata { key: 3 value { id: 3 name: "cond.3"
    stats { metadata_id: 100 str_value: "jit(gwas_fused_step)/gwas.epilogue/gwas.epilogue.compact/cond" } } }
  event_metadata { key: 4 value { id: 4 name: "fusion.3"
    stats { metadata_id: 100 str_value: "jit(gwas_fused_step)/gwas.epilogue/gwas.epilogue.compact/cond/branch_1_fun/cumsum" } } }
  event_metadata { key: 5 value { id: 5 name: "copy.1" } }
  event_metadata { key: 6 value { id: 6 name: "while.5" } }
  event_metadata { key: 7 value { id: 7 name: "fusion.2"
    stats { metadata_id: 100 str_value: "jit(repack_plink_tiled_device)/gwas.device_decode/or" } } }
  event_metadata { key: 8 value { id: 8 name: "jit_gwas_fused_step(11)" } }
  event_metadata { key: 9 value { id: 9 name: "jit_gwas_refine(12)" } }
  stat_metadata { key: 100 value { id: 100 name: "tf_op" } }
}
planes {
  id: 3 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 4000000 } }
  event_metadata { key: 1 value { id: 1 name: "gwas_dot"
    stats { metadata_id: 100 str_value: "jit(gwas_fused_step)/gwas.assoc/pallas_call" } } }
  stat_metadata { key: 100 value { id: 100 name: "tf_op" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 20000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 16000000 }
    events { metadata_id: 3 offset_ps: 0 duration_ps: 500000 stats { metadata_id: 200 str_value: "serial" } }
    events { metadata_id: 4 offset_ps: 5000000 duration_ps: 4200000 stats { metadata_id: 200 str_value: "serial" } }
    events { metadata_id: 5 offset_ps: 10000000 duration_ps: 4000000 stats { metadata_id: 200 str_value: "serial" } }
    events { metadata_id: 6 offset_ps: 10000000 duration_ps: 1000000 stats { metadata_id: 200 str_value: "serial" } }
    events { metadata_id: 7 offset_ps: 11500000 duration_ps: 2300000 stats { metadata_id: 200 str_value: "serial" } }
    events { metadata_id: 8 offset_ps: 14000000 duration_ps: 500000 stats { metadata_id: 200 str_value: "serial" }
             stats { metadata_id: 201 int64_value: 65 } }
    events { metadata_id: 9 offset_ps: 16000000 duration_ps: 4000000 }
    events { metadata_id: 10 offset_ps: 16000000 duration_ps: 3500000 stats { metadata_id: 200 str_value: "-" } }
    events { metadata_id: 11 offset_ps: 0 duration_ps: 1000 } }
  lines { id: 2 name: "slot-decode-0" timestamp_ns: 1000
    events { metadata_id: 12 offset_ps: 0 duration_ps: 20000000 stats { metadata_id: 200 str_value: "serial" } } }
  lines { id: 3 name: "scan-device-1" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 4000000 duration_ps: 15900000 stats { metadata_id: 200 str_value: "dev1" } } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.pull" } }
  event_metadata { key: 3 value { id: 3 name: "gwas.wait_input" } }
  event_metadata { key: 4 value { id: 4 name: "gwas.fence" } }
  event_metadata { key: 5 value { id: 5 name: "gwas.extract" } }
  event_metadata { key: 6 value { id: 6 name: "gwas.pull" } }
  event_metadata { key: 7 value { id: 7 name: "gwas.refine" } }
  event_metadata { key: 8 value { id: 8 name: "gwas.sinks" } }
  event_metadata { key: 9 value { id: 9 name: "bench.write" } }
  event_metadata { key: 10 value { id: 10 name: "gwas.write" } }
  event_metadata { key: 11 value { id: 11 name: "PjRtStream" } }
  event_metadata { key: 12 value { id: 12 name: "gwas.decode" } }
  stat_metadata { key: 200 value { id: 200 name: "slot" } }
  stat_metadata { key: 201 value { id: 201 name: "refine_launches" } }
}
"""
US = 1e-6


def _write(path, text=XSPACE) -> str:
    from jax.profiler import ProfileData

    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(text))
    return str(path)


@pytest.fixture
def trace(tmp_path):
    return ts.load(_write(tmp_path / "t.xplane.pb"))


def test_load_keeps_scopes_spans_threads_and_arguments(trace):
    assert [o.scope for o in trace.ops[0]] == [
        "gwas.assoc", "gwas.assoc", "gwas.epilogue.compact", "gwas.epilogue.compact",
        None, ts.REFINE, "gwas.device_decode"]
    names = [s.name for s in trace.spans]
    assert "PjRtStream" not in names and names.count("gwas.wait_input") == 2
    sinks = next(s for s in trace.spans if s.name == "gwas.sinks")
    assert sinks.args == {"slot": "serial", "refine_launches": 65}
    decode = next(s for s in trace.spans if s.name == "gwas.decode")
    assert decode.line != sinks.line
    assert trace.window() == pytest.approx((1000, 21000))


@pytest.mark.parametrize("scope,seconds", [
    ("gwas.assoc", 6 * US),
    ("gwas.epilogue", 3 * US),          # the conditional and its body op count once
    ("gwas.epilogue.compact", 3 * US),
    ("gwas.epilogue.best", 0.0),
    ("gwas.device_decode", 1 * US),
    (ts.REFINE, 1 * US),
])
def test_scope_time_is_the_union_of_its_ops(trace, scope, seconds):
    assert ts.scope_seconds(trace, scope, [0])[0] == pytest.approx(seconds)


def test_coverage_and_the_unscoped_rest(trace):
    scopes = ("gwas.assoc", "gwas.epilogue", "gwas.device_decode", ts.REFINE)
    assert ts.coverage(trace, scopes, [0]) == pytest.approx(11 / 11.5)
    assert ts.unscoped_ops(trace, [0]) == [("copy.1", pytest.approx(0.5 * US))]


def test_gaps_are_labelled_by_the_innermost_span_covering_most(trace):
    labels = ts.gap_labels(trace, [0])
    assert labels == [
        ("gwas.write", pytest.approx(5 * US)),     # inside bench.write
        ("gwas.extract", pytest.approx(2 * US)),   # pull covers half, not most
        ("gwas.refine", pytest.approx(1 * US)),    # the refine inside the extract
        ("gwas.fence", pytest.approx(0.5 * US)),   # none covers most: the one covering
    ]                                              # the largest part, before bench.pull
    # the decode worker's span, on another thread, labels nothing on one chip
    assert not any("decode" in n for n, _ in labels)


def test_on_several_chips_each_gap_is_labelled_by_its_slot(trace):
    labels = dict(ts.gap_labels(trace, [0, 1]))
    assert labels["TPU:1 gwas.wait_input"] == pytest.approx(16 * US)
    # no dev0 span: the window thread's bench span, as before
    assert labels["TPU:0 bench.write"] == pytest.approx(5 * US)


def test_the_existing_reduction_reads_as_before(tmp_path):
    """trace_reduce on the same file: op sums not merged; its idle gaps are
    the ones gap_labels labels."""
    red = tr.reduce(tr.load(_write(tmp_path / "t.xplane.pb")), devices=[0])
    assert red.busy_s[0] == pytest.approx(11.5 * US)
    assert red.idle_share == pytest.approx(1 - 11.5 / 20)
    ops = dict(red.device_ops)
    assert ops  # named by trace_reduce's own rule, each op's time summed
    assert sum(ops.values()) == pytest.approx(13.5 * US)    # 3 + 2 counted twice
    labelled = ts.gap_labels(ts.load(str(tmp_path / "t.xplane.pb")), [0])
    assert sum(s for _, s in labelled) == pytest.approx(red.window_s - red.busy_s[0])


def test_the_breakdown_labels_idle_gaps_by_the_programs_spans(tmp_path):
    path = _write(tmp_path / "trace" / "plugins" / "profile" / "t" / "h.xplane.pb")
    assert harness.trace_file(str(tmp_path / "trace")) == str(path)
    reduced, breakdown = harness.reduce_trace(str(path), [0])
    assert reduced.idle_share == pytest.approx(1 - 11.5 / 20)
    assert [n for n, _ in breakdown["idle_gaps"]] == [
        "gwas.write", "gwas.extract", "gwas.refine", "gwas.fence"]
    assert breakdown["device_ops"] == [list(x) for x in reduced.device_ops]
    assert harness.trace_file(str(tmp_path / "none")) is None
    empty = _write(tmp_path / "empty.xplane.pb", 'planes { id: 1 name: "/host:CPU" }')
    assert harness.reduce_trace(str(empty), [0]) == (None, None)


def test_a_snapshot_carries_the_programs_counters_and_span_totals():
    from repro.api.metrics import CellTiming, ScanMetrics

    m = ScanMetrics()
    m.record(CellTiming(batch_index=0, block_index=0, n_markers=8, n_traits=4, wall_s=0.5,
                        refine_launches=3, hits=2))
    m.fold_span("wait_input", 0.25)
    m.fold_span("wait_input", 0.5)
    snap = harness.scan_snapshot(m)
    assert snap["counters"]["refine_launches"] == 3 and snap["counters"]["hits"] == 2
    assert snap["spans"]["wait_input"] == (0.75, 2)
    assert (snap["markers"], snap["cells"]) == (8, 1)


# --------------------------------------------------------------- the readers


def _run(tmp_path, monkeypatch, text=XSPACE, traced=True):
    import tempfile

    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    path = _write(tmp_path / "gwasbench_x" / "trace" / "plugins" / "profile" / "t" /
                  "h.xplane.pb", text)
    cell = harness.Cell(name="c", chips=1, config={"scan": {"batch_markers": 8192}},
                        traffic={})
    reduced = tr.reduce(tr.load(path), devices=[0]) if traced else None
    return harness.Run(cell=cell, spans=harness.Spans(), compiles=None, started=0.0,
                       window=(0.0, 20 * US), window_cells=[(8192, 20480)] * 2,
                       scan_before={"markers": 0}, scan_after={"markers": 16384},
                       trace=reduced, trace_path=str(path))


READERS = {
    "assoc_ms_per_cell": 1e3 * 6 * US / 2,
    "epilogue_ms_per_cell": 1e3 * 3 * US / 2,
    "device_decode_ms_per_batch": 1e3 * 1 * US / 2,
    "refine_launches_per_cell": 65.0,
    "input_wait_ms_per_cell": 1e3 * (0.5 + 15.9) * US / 2,     # every slot's waits
}


@pytest.mark.parametrize("name", sorted(READERS))
def test_each_reader_reads_a_synthetic_run(tmp_path, monkeypatch, name):
    value = harness.load_reader(name)(_run(tmp_path, monkeypatch))
    assert value == pytest.approx(READERS[name])


def _without_program_names(text: str) -> str:
    """The same trace as a program without spans or scopes would leave it."""
    out = text.replace("gwas.assoc", "x").replace("gwas.epilogue", "x")
    out = out.replace("gwas.device_decode", "x").replace("gwas_refine", "x")
    for span in ("wait_input", "fence", "extract", "pull", "refine", "sinks", "write",
                 "decode"):
        out = out.replace(f'"gwas.{span}"', f'"other.{span}"')
    return out


@pytest.mark.parametrize("name", sorted(READERS))
def test_each_reader_reads_nothing_without_the_programs_names(tmp_path, monkeypatch, name):
    read = harness.load_reader(name)
    assert read(_run(tmp_path, monkeypatch, _without_program_names(XSPACE))) is None
    assert read(_run(tmp_path, monkeypatch, traced=False)) is None


def test_a_stale_trace_is_not_read(tmp_path, monkeypatch):
    run = _run(tmp_path, monkeypatch)
    assert ts.of(run) is not None
    # a run whose profiler wrote no file reads none, not another run's file
    # left in the temporary directory
    run.trace_path = None
    assert ts.of(run) is None
