"""From a profiler trace (``.xplane.pb``) to device busy time, idle share
and the device operations that took the most time.

Busy time on a device is the union of the intervals in which an operation
of its ``XLA Ops`` line ran, clipped to the traced window; the window is
the benchmark's own ``bench.window`` host span, on the same clock.  The
idle share is ``1 - busy / window``.  The idle gaps between those
intervals (``gaps``) are labelled by the program's own spans in
``trace_scopes.gap_labels``.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
TOP = 10


@dataclass
class TraceEvents:
    """Intervals in nanoseconds on the trace's clock."""

    ops: dict[int, list[tuple[float, float, str]]] = field(default_factory=dict)
    spans: list[tuple[float, float, str]] = field(default_factory=list)


def load(path: str) -> TraceEvents:
    from jax.profiler import ProfileData

    out = TraceEvents()
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                out.ops.setdefault(int(m.group(1)), []).extend(
                    (e.start_ns, e.start_ns + e.duration_ns, e.name) for e in line.events)
            elif not m:
                out.spans.extend(
                    (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in line.events if e.name.startswith(SPAN_PREFIX))
    return out


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Disjoint, sorted union of ``(start, end, ...)`` clipped to [lo, hi]."""
    merged: list[list[float]] = []
    for s, e, *_ in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def gaps(busy: list[tuple[float, float]], lo: float, hi: float) -> list[tuple[float, float]]:
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    return [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]


@dataclass
class Reduced:
    window_s: float
    busy_s: dict[int, float]               # per device
    device_ops: list[tuple[str, float]]    # name, seconds (summed over devices)

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s.values()) / len(self.busy_s)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.mean_busy_s / self.window_s


def reduce(ev: TraceEvents, devices: list[int] | None = None) -> Reduced | None:
    """None when the trace holds no window span or no device operation."""
    windows = [(s, e) for s, e, name in ev.spans if name == WINDOW_SPAN]
    devices = sorted(ev.ops) if devices is None else devices
    if not windows or not devices or not any(ev.ops.get(d) for d in devices):
        return None
    lo, hi = windows[0]
    busy, per_op = {}, defaultdict(float)
    for d in devices:
        ops = ev.ops.get(d, [])
        busy[d] = sum(e - s for s, e in union(ops, lo, hi)) * 1e-9
        for s, e, name in ops:
            per_op[name] += max(0.0, min(e, hi) - max(s, lo)) * 1e-9
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    return Reduced(window_s=(hi - lo) * 1e-9, busy_s=busy, device_ops=top_ops)
