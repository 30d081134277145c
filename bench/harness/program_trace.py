"""The program's own spans and device scopes, read from a ``--trace 1``
run's ``.xplane.pb``.

``harness.trace`` reads a trace through ``jax.profiler.ProfileData``,
which gives each event's name and arguments but not the metadata of a
device op: the HLO ``op_name`` path (``jit(run)/while/body/...``) in
which a ``jax.named_scope`` of the program shows (``repro.gap``,
``repro.pad``).  This module decodes the file itself, with the part of
the profiler's ``XSpace`` schema it needs built for ``google.protobuf``,
and gives

* the host plane's ``repro.*`` spans and ``repro.compile`` markers
  (``repro.metrics.spans``) with their arguments, those that lie wholly
  inside the traced window (the ``bench.window`` span);
* each device op's ``op_name`` (the ``tf_op`` stat of its event
  metadata), for the device ops ``harness.trace`` counts (container ops
  dropped).

A run is read only where ``run.device_trace`` is set, that is where the
trace covers its window.  A program without these spans or scopes (one
older than them) reads as a trace that holds none: the readers then
report nothing.
"""
from __future__ import annotations

import pathlib
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

from . import trace

SPAN_PREFIX = "repro."
COMPILE_MARKER = "repro.compile"
OP_NAME_STAT = "tf_op"

Interval = Tuple[str, float, float]           # (op_name, start_ns, end_ns)


class Span(NamedTuple):
    name: str
    start: float                              # ns, the device ops' clock
    end: float
    args: dict

    @property
    def ms(self) -> float:
        return (self.end - self.start) / 1e6


# ---------------------------------------------------------------------------
# The schema: the fields of tsl/profiler/protobuf/xplane.proto read here
# (a map field is a repeated entry message on the wire)
# ---------------------------------------------------------------------------

_FIELDS = {
    "XStat": [("metadata_id", 1, "int64"), ("double_value", 2, "double"),
              ("uint64_value", 3, "uint64"), ("int64_value", 4, "int64"),
              ("str_value", 5, "string"), ("bytes_value", 6, "bytes"),
              ("ref_value", 7, "uint64")],
    "XEvent": [("metadata_id", 1, "int64"), ("offset_ps", 2, "int64"),
               ("duration_ps", 3, "int64"), ("stats", 4, "XStat*")],
    "XLine": [("id", 1, "int64"), ("name", 2, "string"),
              ("timestamp_ns", 3, "int64"), ("events", 4, "XEvent*")],
    "XEventMetadata": [("id", 1, "int64"), ("name", 2, "string"),
                       ("stats", 5, "XStat*")],
    "XStatMetadata": [("id", 1, "int64"), ("name", 2, "string")],
    "EventMetadataEntry": [("key", 1, "int64"),
                           ("value", 2, "XEventMetadata")],
    "StatMetadataEntry": [("key", 1, "int64"), ("value", 2, "XStatMetadata")],
    "XPlane": [("id", 1, "int64"), ("name", 2, "string"),
               ("lines", 3, "XLine*"),
               ("event_metadata", 4, "EventMetadataEntry*"),
               ("stat_metadata", 5, "StatMetadataEntry*"),
               ("stats", 6, "XStat*")],
    "XSpace": [("planes", 1, "XPlane*")],
}
_VALUE_FIELDS = ("double_value", "uint64_value", "int64_value", "str_value",
                 "bytes_value", "ref_value")       # XStat's oneof "value"


def _space_class():
    from google.protobuf import descriptor_pb2, descriptor_pool, \
        message_factory
    F = descriptor_pb2.FieldDescriptorProto
    scalar = {"int64": F.TYPE_INT64, "uint64": F.TYPE_UINT64,
              "double": F.TYPE_DOUBLE, "string": F.TYPE_STRING,
              "bytes": F.TYPE_BYTES}
    fd = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane_subset.proto", package="bench_xplane",
        syntax="proto3")
    for message, fields in _FIELDS.items():
        m = fd.message_type.add(name=message)
        if message == "XStat":
            m.oneof_decl.add(name="value")
        for name, number, kind in fields:
            f = m.field.add(name=name, number=number)
            repeated = kind.endswith("*")
            kind = kind.rstrip("*")
            f.label = F.LABEL_REPEATED if repeated else F.LABEL_OPTIONAL
            if kind in scalar:
                f.type = scalar[kind]
            else:
                f.type, f.type_name = F.TYPE_MESSAGE, f".bench_xplane.{kind}"
            if message == "XStat" and name in _VALUE_FIELDS:
                f.oneof_index = 0
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


_XSPACE = None


def parse(data: bytes):
    """An ``XSpace`` message (the schema subset above) from bytes."""
    global _XSPACE
    if _XSPACE is None:
        _XSPACE = _space_class()
    space = _XSPACE()
    space.ParseFromString(data)
    return space


def _stat_value(stat, stat_names: Dict[int, str]):
    which = stat.WhichOneof("value")
    if which == "ref_value":
        return stat_names.get(stat.ref_value, "")
    return getattr(stat, which) if which else None


def _names(plane) -> Dict[int, str]:
    return {e.key: e.value.name for e in plane.stat_metadata}


# ---------------------------------------------------------------------------
# The program's view of one trace
# ---------------------------------------------------------------------------

class ProgramTrace:
    """The spans and device ops of one ``XSpace``; the spans are those
    wholly inside the traced window (the ``bench.window`` span, else the
    extent of everything recorded)."""

    def __init__(self, space):
        self._space = space
        self._ops: Optional[Dict[str, List[Interval]]] = None
        spans, window = [], None
        for plane in space.planes:
            if not plane.name.startswith("/host"):
                continue
            stat_names = _names(plane)
            events = {e.key: e.value.name for e in plane.event_metadata}
            for line in plane.lines:
                for ev in line.events:
                    name = events.get(ev.metadata_id, "")
                    if not name.startswith((SPAN_PREFIX, trace.SPAN_PREFIX)):
                        continue
                    start = line.timestamp_ns + ev.offset_ps / 1e3
                    end = start + ev.duration_ps / 1e3
                    if name == trace.SPAN_PREFIX + "window":
                        window = (start, end)
                    elif name.startswith(SPAN_PREFIX):
                        spans.append(Span(name, start, end, {
                            stat_names.get(s.metadata_id, ""):
                                _stat_value(s, stat_names)
                            for s in ev.stats}))
        spans.sort(key=lambda s: (s.start, -s.end))
        if window is None:
            every = [(s.start, s.end) for s in spans] + [
                (s, e) for evs in self.device_ops().values()
                for _, s, e in evs]
            window = (min((s for s, _ in every), default=0.0),
                      max((e for _, e in every), default=0.0))
        self.window = window
        lo, hi = self.window
        self.spans = [s for s in spans if lo <= s.start and s.end <= hi]

    def named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]

    def device_ops(self) -> Dict[str, List[Interval]]:
        """Per device plane, its ops as (op_name, start, end), container
        ops dropped as ``harness.trace`` drops them; an op with no
        ``op_name`` reads ``""``."""
        if self._ops is None:
            self._ops = {}
            for plane in self._space.planes:
                if not plane.name.startswith("/device:TPU"):
                    continue
                stat_names = _names(plane)
                op_name = {}
                for e in plane.event_metadata:
                    op_name[e.key] = next(
                        (str(_stat_value(s, stat_names))
                         for s in e.value.stats
                         if stat_names.get(s.metadata_id) == OP_NAME_STAT),
                        "")
                for line in plane.lines:
                    if line.name != trace.OPS_LINE:
                        continue
                    t0 = line.timestamp_ns
                    self._ops[plane.name] = trace.leaves([
                        (op_name.get(ev.metadata_id, ""),
                         t0 + ev.offset_ps / 1e3,
                         t0 + (ev.offset_ps + ev.duration_ps) / 1e3)
                        for ev in line.events])
        return self._ops

    def op_seconds(self, select: Callable[[str], bool]) -> float:
        """Device seconds of the ops whose ``op_name`` ``select`` picks,
        inside the window, summed over the chips."""
        lo, hi = self.window
        return sum(min(e, hi) - max(s, lo)
                   for evs in self.device_ops().values()
                   for name, s, e in evs
                   if select(name) and min(e, hi) > max(s, lo)) / 1e9

    def scoped(self) -> bool:
        """Whether any device op carries one of the program's scopes."""
        return any(SPAN_PREFIX in name for evs in self.device_ops().values()
                   for name, _, _ in evs)


def read_file(path) -> ProgramTrace:
    return ProgramTrace(parse(pathlib.Path(path).read_bytes()))


_LOADED: Dict[tuple, ProgramTrace] = {}


def load(run) -> Optional[ProgramTrace]:
    """The newest ``.xplane.pb`` under ``run.work_dir``, or None where
    the run kept no trace that covers its window."""
    if run.device_trace is None:
        return None
    paths = sorted(pathlib.Path(run.work_dir).glob(
        "plugins/profile/*/*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not paths:
        return None
    key = (str(paths[-1]), paths[-1].stat().st_mtime_ns)
    if key not in _LOADED:
        _LOADED.clear()
        _LOADED[key] = read_file(paths[-1])
    return _LOADED[key]


# ---------------------------------------------------------------------------
# What the per-layer readers take from it
# ---------------------------------------------------------------------------

def child_ms_per(run, child: str, parent: str) -> Optional[float]:
    """Mean over the window's ``parent`` spans of the milliseconds of the
    ``child`` spans inside each; None where the window holds no
    ``parent`` span."""
    t = load(run)
    parents = t.named(parent) if t else []
    if not parents:
        return None
    children = t.named(child)
    return sum(c.ms for p in parents for c in children
               if p.start <= c.start and c.end <= p.end) / len(parents)


def ms_per(run, name: str, per: str) -> Optional[float]:
    """The window's ``name`` spans' milliseconds over its count of
    ``per`` spans; None where it holds no ``per`` span."""
    t = load(run)
    count = len(t.named(per)) if t else 0
    if not count:
        return None
    return sum(s.ms for s in t.named(name)) / count


def count_per(run, name: str, per: str) -> Optional[float]:
    """The window's count of ``name`` spans or markers over its count of
    ``per`` spans; None where it holds no ``per`` span."""
    t = load(run)
    count = len(t.named(per)) if t else 0
    if not count:
        return None
    return len(t.named(name)) / count


def scope_ms_per_round(run, scope: str) -> Optional[float]:
    """Device milliseconds a round of the ops whose ``op_name`` holds
    ``scope``.  None where the trace does not span the whole window (its
    rounds are then not the window's) or where no device op carries any
    of the program's scopes (none reached the trace); 0 where scopes
    did but this one is gone."""
    t = load(run)
    rounds = run.counters.get("rounds")
    if t is None or not rounds or run.counters.get("traced_from_s", 0.0) > 0.0:
        return None
    if not t.scoped():
        return None
    return 1e3 * t.op_seconds(lambda name: scope in name) / rounds
