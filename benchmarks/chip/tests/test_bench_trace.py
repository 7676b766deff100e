"""Trace reduction: intervals, idle share, breakdown, and reading an xplane."""
import glob
import os

import pytest

import bench_tiny  # noqa: F401
import trace_reduce as tr


def test_union_merges_overlaps_and_clips():
    ops = [(0, 10, "a"), (5, 15, "b"), (20, 30, "c"), (28, 50, "d"), (60, 70, "e")]
    assert tr.union(ops, 2, 45) == [(2, 15), (20, 45)]
    assert tr.gaps(tr.union(ops, 2, 45), 2, 45) == [(15, 20)]
    assert tr.gaps([], 0, 5) == [(0, 5)]


def _events():
    ev = tr.TraceEvents()
    ev.spans = [(0, 1000, "bench.window"), (100, 300, "bench.pull"), (300, 700, "bench.write")]
    # device 0 busy 0-100 and 320-600: gaps 100-320 (mostly pull) and 600-1000 (write)
    ev.ops[0] = [(0, 100, "fusion.1"), (320, 500, "gwas_dot"), (450, 600, "gwas_dot")]
    return ev


def test_idle_share_and_breakdown_order():
    red = tr.reduce(_events())
    assert red.window_s == pytest.approx(1000e-9)
    assert red.busy_s[0] == pytest.approx(380e-9)
    assert red.idle_share == pytest.approx(0.62)
    assert [n for n, _ in red.device_ops] == ["gwas_dot", "fusion.1"]
    assert red.device_ops[0][1] == pytest.approx(330e-9)   # op time, overlaps not merged
    # the idle gaps between the busy intervals (trace_scopes labels them)
    assert tr.gaps(tr.union(_events().ops[0], 0, 1000), 0, 1000) == [(100, 320), (600, 1000)]


def test_devices_are_averaged_and_labelled():
    ev = _events()
    ev.ops[1] = [(0, 1000, "gwas_dot")]
    red = tr.reduce(ev, devices=[0, 1])
    assert red.mean_busy_s == pytest.approx(690e-9)
    assert dict(red.device_ops)["gwas_dot"] == pytest.approx(330e-9 + 1000e-9)  # summed
    # each device's gaps are labelled by its slot's spans, under its name
    import trace_scopes as ts

    spans = [ts.Span(0, 1000, "bench.window", (1, 0)),
             ts.Span(100, 320, "gwas.wait_input", (1, 1), {"slot": "dev0"}),
             ts.Span(600, 1000, "gwas.extract", (1, 1), {"slot": "dev0"}),
             ts.Span(0, 1000, "gwas.fence", (1, 2), {"slot": "dev1"})]
    ops = {d: [ts.Op(s, e, n, None) for s, e, n in ev.ops[d]] for d in (0, 1)}
    assert ts.gap_labels(ts.Scoped(ops=ops, spans=spans), [0, 1]) == [
        ("TPU:0 gwas.extract", pytest.approx(400e-9)),
        ("TPU:0 gwas.wait_input", pytest.approx(220e-9))]


def test_nothing_to_read_gives_none():
    ev = _events()
    assert tr.reduce(tr.TraceEvents(spans=ev.spans)) is None
    assert tr.reduce(tr.TraceEvents(ops=ev.ops)) is None


XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 9000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.7" } }
  event_metadata { key: 2 value { id: 2 name: "gwas_dot" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 3000000 }
    events { metadata_id: 3 offset_ps: 0 duration_ps: 1000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.write" } }
  event_metadata { key: 3 value { id: 3 name: "PjRtStream" } }
}
"""


def test_load_reads_device_ops_and_bench_spans(tmp_path):
    from jax.profiler import ProfileData

    import trace_scopes

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    ev = tr.load(str(path))
    assert [n for *_, n in ev.ops[0]] == ["fusion.7", "gwas_dot"]
    assert sorted(n for *_, n in ev.spans) == ["bench.window", "bench.write"]
    red = tr.reduce(ev)
    assert red.busy_s[0] == pytest.approx(3e-6)
    assert red.idle_share == pytest.approx(0.7)
    # no program span in this trace: the breakdown's gaps fall back to bench spans
    labels = trace_scopes.gap_labels(trace_scopes.load(str(path)), [0])
    assert labels[0] == ("no span", pytest.approx(4e-6))
    assert labels[1] == ("bench.write", pytest.approx(3e-6))


def test_a_recorded_host_trace_yields_its_spans(tmp_path):
    import jax
    import jax.numpy as jnp
    from harness import Spans

    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    spans = Spans()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with spans("window"):
        for _ in range(3):
            with spans("write"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"), recursive=True)
    ev = tr.load(path)
    names = [n for *_, n in ev.spans]
    assert names.count("bench.write") == 3 and names.count("bench.window") == 1
    assert len(spans.durations("write")) == 3
    assert tr.reduce(ev) is None          # no TPU plane on the CPU: nothing device-side
