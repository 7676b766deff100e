"""The program's own names in a profiler trace (``.xplane.pb``): its host
spans, its device scopes, and the idle gaps they explain.

The scan opens a host span ``gwas.<name>`` at each layer boundary
(``repro.api.metrics.SPANS``), each carrying its executor slot (``slot``:
``serial``, ``dev<i>``) and, on ``gwas.sinks``, the cell's counters as
arguments.  Its jitted steps put every device op under a ``jax.named_scope``
(``gwas.device_decode``, ``gwas.assoc``, ``gwas.epilogue`` and its children),
which reaches the trace as the op's ``op_name`` metadata; the canonical
refine is its own executable, ``gwas_refine``.

Device time under a scope is the union of its ops' intervals per device,
clipped to the window (the benchmark's ``bench.window`` span), so a
conditional and the body ops nested in it count once.  An idle gap is
labelled by the innermost span covering most of it: on one chip the spans
of the thread that holds ``bench.window``, on several the spans of the slot
computing on that device, else a ``bench.*`` span of the window's thread.

This reads the trace file itself, beside ``trace_reduce`` (whose busy
time and device ops it leaves as they are); the result line's
``breakdown.idle_gaps`` is ``gap_labels``.  A metric reader reads its
run's file (``run.trace_path``) with ``of(run)``.
"""
from __future__ import annotations

import bisect
import re
from dataclasses import dataclass, field

import trace_reduce

GWAS = "gwas."
BENCH = trace_reduce.SPAN_PREFIX
WINDOW = trace_reduce.WINDOW_SPAN
REFINE = "gwas_refine"
MODULES_LINE = "XLA Modules"
OP_NAME_STAT = "tf_op"          # the metadata stat that carries an op's op_name
# A scope is one component of the op_name path: "…/gwas.assoc/pad".
SCOPE_PART = re.compile(r"(?:^|/)(gwas\.[A-Za-z0-9_.]+)(?=/|$)")
TOP = trace_reduce.TOP


@dataclass(frozen=True)
class Span:
    start: float
    end: float
    name: str
    line: tuple[int, int]           # (plane, line) index: one host thread
    args: dict = field(default_factory=dict, compare=False)

    @property
    def slot(self) -> str | None:
        return self.args.get("slot")


@dataclass(frozen=True)
class Op:
    start: float
    end: float
    name: str
    scope: str | None               # innermost gwas.* scope, REFINE, or None


@dataclass
class Scoped:
    """Intervals in nanoseconds on the trace's clock."""

    ops: dict[int, list[Op]] = field(default_factory=dict)
    spans: list[Span] = field(default_factory=list)

    def window(self) -> tuple[float, float] | None:
        found = [(s.start, s.end) for s in self.spans if s.name == WINDOW]
        return found[0] if found else None


def op_scope(op_name: str) -> str | None:
    """``REFINE`` for an op of the refine executable, else the innermost
    ``gwas.*`` component of the op's ``op_name``, else None."""
    if REFINE in op_name:
        return REFINE
    parts = SCOPE_PART.findall(op_name)
    return parts[-1] if parts else None


def in_scope(scope_of_op: str | None, scope: str) -> bool:
    return scope_of_op is not None and (
        scope_of_op == scope or scope_of_op.startswith(scope + ".")
    )


def _xspace():
    """The ``XSpace`` message class, built from the field numbers of the
    profiler's public ``xplane.proto``.  ``jax.profiler.ProfileData`` shows
    an event's own stats but not its metadata's, where a device op's
    ``op_name`` lives, so the file is read here whole."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    T = descriptor_pb2.FieldDescriptorProto
    f = descriptor_pb2.FileDescriptorProto(name="gwasbench_xplane.proto",
                                           package="gwasbench", syntax="proto3")

    def message(name, *fields):
        m = f.message_type.add(name=name)
        for fname, number, ftype, rep, *tname in fields:
            m.field.add(name=fname, number=number, type=ftype,
                        label=T.LABEL_REPEATED if rep else T.LABEL_OPTIONAL,
                        type_name=f".gwasbench.{tname[0]}" if tname else None)
        return m

    stat = message("XStat", ("metadata_id", 1, T.TYPE_INT64, 0),
                   ("double_value", 2, T.TYPE_DOUBLE, 0), ("uint64_value", 3, T.TYPE_UINT64, 0),
                   ("int64_value", 4, T.TYPE_INT64, 0), ("str_value", 5, T.TYPE_STRING, 0),
                   ("ref_value", 7, T.TYPE_UINT64, 0))
    stat.oneof_decl.add(name="value")
    for fd in stat.field[1:]:
        fd.oneof_index = 0
    message("XEvent", ("metadata_id", 1, T.TYPE_INT64, 0), ("offset_ps", 2, T.TYPE_INT64, 0),
            ("duration_ps", 3, T.TYPE_INT64, 0), ("stats", 4, T.TYPE_MESSAGE, 1, "XStat"))
    message("XLine", ("name", 2, T.TYPE_STRING, 0), ("timestamp_ns", 3, T.TYPE_INT64, 0),
            ("events", 4, T.TYPE_MESSAGE, 1, "XEvent"))
    message("XEventMetadata", ("id", 1, T.TYPE_INT64, 0), ("name", 2, T.TYPE_STRING, 0),
            ("display_name", 4, T.TYPE_STRING, 0), ("stats", 5, T.TYPE_MESSAGE, 1, "XStat"))
    message("XStatMetadata", ("id", 1, T.TYPE_INT64, 0), ("name", 2, T.TYPE_STRING, 0))
    message("EventMetadataEntry", ("key", 1, T.TYPE_INT64, 0),
            ("value", 2, T.TYPE_MESSAGE, 0, "XEventMetadata"))
    message("StatMetadataEntry", ("key", 1, T.TYPE_INT64, 0),
            ("value", 2, T.TYPE_MESSAGE, 0, "XStatMetadata"))
    message("XPlane", ("name", 2, T.TYPE_STRING, 0), ("lines", 3, T.TYPE_MESSAGE, 1, "XLine"),
            ("event_metadata", 4, T.TYPE_MESSAGE, 1, "EventMetadataEntry"),
            ("stat_metadata", 5, T.TYPE_MESSAGE, 1, "StatMetadataEntry"))
    message("XSpace", ("planes", 1, T.TYPE_MESSAGE, 1, "XPlane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(pool.FindMessageTypeByName("gwasbench.XSpace"))


def _stats(stats, stat_names: dict) -> dict:
    """Stat name -> value (a ``ref_value`` names an interned string)."""
    out = {}
    for st in stats:
        kind = st.WhichOneof("value")
        v = getattr(st, kind) if kind else None
        if kind == "ref_value":
            v = stat_names.get(v, "")
        out[stat_names.get(st.metadata_id, str(st.metadata_id))] = v
    return out


def load(path: str) -> Scoped:
    space = _xspace()()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    out = Scoped()
    for pi, plane in enumerate(space.planes):
        m = trace_reduce.DEVICE_PLANE.match(plane.name)
        if m:
            out.ops[int(m.group(1))] = _device_ops(plane)
        else:
            out.spans += _host_spans(plane, pi)
    return out


def _runs(line, meta) -> list[tuple[float, float, str]]:
    """A line's events as (start, end, metadata name), in nanoseconds."""
    t0 = line.timestamp_ns
    return [(t0 + ev.offset_ps * 1e-3, t0 + (ev.offset_ps + ev.duration_ps) * 1e-3,
             meta[ev.metadata_id].name if ev.metadata_id in meta else "")
            for ev in line.events]


def _device_ops(plane) -> list[Op]:
    stat_names = {e.key: e.value.name for e in plane.stat_metadata}
    meta = {e.key: e.value for e in plane.event_metadata}
    named: dict[int, tuple[str, str | None]] = {}      # metadata id -> (name, scope)
    ops: list[Op] = []
    refine_runs: list[tuple[float, float, str]] = []
    for line in plane.lines:
        if line.name == MODULES_LINE:
            refine_runs += [r for r in _runs(line, meta) if REFINE in r[2]]
        elif line.name == trace_reduce.OPS_LINE:
            t0 = line.timestamp_ns
            for ev in line.events:
                if ev.metadata_id not in named:        # op_name is a metadata stat
                    md = meta.get(ev.metadata_id)
                    name = (md.display_name or md.name) if md is not None else ""
                    stats = _stats(md.stats, stat_names) if md is not None else {}
                    named[ev.metadata_id] = (name, op_scope(str(stats.get(OP_NAME_STAT, ""))))
                start = t0 + ev.offset_ps * 1e-3
                ops.append(Op(start, start + ev.duration_ps * 1e-3, *named[ev.metadata_id]))
    return _refine_by_module(ops, sorted(refine_runs)) if refine_runs else ops


def _host_spans(plane, pi: int) -> list[Span]:
    """The ``gwas.*`` and ``bench.*`` events of a host plane, each with its
    (plane, line) thread and its arguments."""
    stat_names = {e.key: e.value.name for e in plane.stat_metadata}
    keep = {e.key: e.value.name for e in plane.event_metadata
            if e.value.name.startswith(GWAS) or e.value.name.startswith(BENCH)}
    spans = []
    for li, line in enumerate(plane.lines):
        t0 = line.timestamp_ns
        for ev in line.events:
            name = keep.get(ev.metadata_id)
            if name is not None:
                start = t0 + ev.offset_ps * 1e-3
                spans.append(Span(start, start + ev.duration_ps * 1e-3, name, (pi, li),
                                  _stats(ev.stats, stat_names)))
    return spans


def _refine_by_module(ops: list[Op], runs: list[tuple[float, float, str]]) -> list[Op]:
    """Ops with no op_name (XLA drops it from some loops, as the refine's
    ``while``) that ran inside a run of the refine executable are its ops."""
    starts = [s for s, *_ in runs]
    out = []
    for o in ops:
        i = bisect.bisect_right(starts, o.start) - 1
        if o.scope is None and i >= 0 and o.end <= runs[i][1]:
            o = Op(o.start, o.end, o.name, REFINE)
        out.append(o)
    return out


def _clip(iv, lo: float, hi: float) -> float:
    return max(0.0, min(iv.end, hi) - max(iv.start, lo))


def scope_seconds(tr: Scoped, scope: str, devices: list[int]) -> dict[int, float]:
    """Per device: seconds of the union of ``scope``'s op intervals in the
    window (``REFINE`` for the refine executable)."""
    lo, hi = tr.window()
    out = {}
    for d in devices:
        ops = [(o.start, o.end) for o in tr.ops.get(d, []) if in_scope(o.scope, scope)]
        out[d] = sum(e - s for s, e in trace_reduce.union(ops, lo, hi)) * 1e-9
    return out


def coverage(tr: Scoped, scopes: tuple[str, ...], devices: list[int]) -> float | None:
    """Share of the window's device busy time under any of ``scopes``."""
    lo, hi = tr.window()
    busy = named = 0.0
    for d in devices:
        ops = tr.ops.get(d, [])
        busy += sum(e - s for s, e in trace_reduce.union(
            [(o.start, o.end) for o in ops], lo, hi))
        named += sum(e - s for s, e in trace_reduce.union(
            [(o.start, o.end) for o in ops
             if any(in_scope(o.scope, sc) for sc in scopes)], lo, hi))
    return named / busy if busy > 0 else None


def unscoped_ops(tr: Scoped, devices: list[int]) -> list[tuple[str, float]]:
    """The ops in no ``gwas`` scope, by seconds in the window (summed)."""
    lo, hi = tr.window()
    per: dict[str, float] = {}
    for d in devices:
        for o in tr.ops.get(d, []):
            if o.scope is None:
                per[o.name] = per.get(o.name, 0.0) + _clip(o, lo, hi) * 1e-9
    return sorted(per.items(), key=lambda kv: -kv[1])[:TOP]


def spans_in_window(tr: Scoped, name: str) -> list[Span]:
    """The ``gwas.<name>`` spans that lie wholly inside the window."""
    lo, hi = tr.window()
    return [s for s in tr.spans if s.name == GWAS + name and s.start >= lo and s.end <= hi]


def span_seconds(tr: Scoped, name: str) -> float:
    """Seconds of ``gwas.<name>`` spans inside the window, all threads."""
    lo, hi = tr.window()
    return sum(_clip(s, lo, hi) for s in tr.spans if s.name == GWAS + name) * 1e-9


def _innermost(gap: tuple[float, float], spans: list[Span]) -> str | None:
    """The shortest span covering more than half of ``gap`` (spans on one
    thread nest, so that is the innermost of those); else the span covering
    the most of it, the shorter on a tie; None when no span overlaps it."""
    lo, hi = gap
    covers = [(s, _clip(s, lo, hi)) for s in spans]
    covers = [(s, c) for s, c in covers if c > 0]
    if not covers:
        return None
    most = [s for s, c in covers if 2 * c > hi - lo]
    if most:
        return min(most, key=lambda s: s.end - s.start).name
    return max(covers, key=lambda sc: (sc[1], sc[0].start - sc[0].end))[0].name


def gap_labels(tr: Scoped, devices: list[int]) -> list[tuple[str, float]]:
    """The longest idle gaps of the window, each labelled by the program's
    own spans (see the module docstring); ``TPU:<d>`` prefixes on several
    devices."""
    lo, hi = tr.window()
    window_line = next(s.line for s in tr.spans if s.name == WINDOW)
    bench = [s for s in tr.spans
             if s.line == window_line and s.name.startswith(BENCH) and s.name != WINDOW]
    gaps = []
    for i, d in enumerate(devices):
        if len(devices) > 1:
            own = [s for s in tr.spans if s.name.startswith(GWAS) and s.slot == f"dev{i}"]
            prefix = f"TPU:{d} "
        else:
            own = [s for s in tr.spans if s.line == window_line and s.name.startswith(GWAS)]
            prefix = ""
        busy = trace_reduce.union([(o.start, o.end) for o in tr.ops.get(d, [])], lo, hi)
        gaps += [((g[1] - g[0]) * 1e-9, g, prefix, own) for g in trace_reduce.gaps(busy, lo, hi)]
    # Only the longest are labelled: a window holds tens of thousands of gaps.
    longest = sorted(gaps, key=lambda x: -x[0])[:TOP]
    return [(prefix + (_innermost(g, own) or _innermost(g, bench) or "no span"), seconds)
            for seconds, g, prefix, own in longest]


# ------------------------------------------------------- from a metric reader

_LOADED: dict[str, Scoped] = {}


def load_once(path: str) -> Scoped:
    """``load(path)``, read once per process (the harness and every reader
    of a traced run share it)."""
    if path not in _LOADED:
        _LOADED[path] = load(path)
    return _LOADED[path]


def of(run) -> Scoped | None:
    """The run's trace (``run.trace_path``) with the program's names, or
    None when the run was not traced or the program opened no ``gwas.*``
    span in its window."""
    if run.trace is None or run.trace_path is None:
        return None
    tr = load_once(run.trace_path)
    lo, hi = tr.window()
    if not any(s.name.startswith(GWAS) and s.start >= lo and s.end <= hi for s in tr.spans):
        return None
    return tr


def devices_of(run) -> list[int]:
    """The cell's devices, in slot order (as the harness reduced them)."""
    return list(run.trace.busy_s)


def per_cell_ms(run, scope: str) -> float | None:
    """Device milliseconds under ``scope`` per window cell, summed over the
    cell's devices; None where the trace names no op in the scope."""
    tr = of(run)
    if tr is None or not run.window_cells:
        return None
    devices = devices_of(run)
    if not any(in_scope(o.scope, scope) for d in devices for o in tr.ops.get(d, [])):
        return None
    return 1e3 * sum(scope_seconds(tr, scope, devices).values()) / len(run.window_cells)
