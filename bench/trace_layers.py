"""Device time by engine layer, and idle time by the host span open in it.

The engine's jitted programs name each layer with a ``dscep.<layer>``
scope (``repro.obs.trace.LAYERS``), which the compiler keeps in the
``op_name`` of every HLO instruction.  A TPU trace names each op of a
device's ``XLA Ops`` line by its instruction alone; the ``op_name`` is in
the compiled module, which the trace keeps on its ``/host:metadata``
plane, one per program, named as the ``XLA Modules`` line names the
program's runs (instruction names repeat across programs).  The
program's own spans (``dscep.*``, profiler annotations when a query is
registered with spans on) and the benchmark's (``bench.*``) sit on the
host plane of the same trace.  From a raw trace of one window:

* self time: every instant in which the busiest chip runs an op goes to
  the innermost op running then, so a ``while`` and the ops of its body
  are counted once;
* an op's layer is the innermost ``dscep.<layer>`` scope of its
  ``op_name``; an op with none (an XLA-inserted copy) takes the layer of
  the op enclosing it, else counts as ``unscoped``, so the layers and
  ``unscoped`` add up to the busy time;
* idle gaps are named by the innermost ``dscep.*`` or ``bench.*`` host
  span open at their midpoint.

For a trace kept by ``bench/run.py --keep-trace <dir>``::

    PYTHONPATH=src python3 -m bench.trace_layers <dir>/<host>.xplane.pb
"""
from __future__ import annotations

import bisect
import functools
import gzip
import json
import re
import sys
from collections import defaultdict
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

from repro.obs.trace import LAYERS, SCOPE_PREFIX

from bench import trace_reduce as TR

MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
UNSCOPED = "unscoped"
_SCOPE = re.compile(re.escape(SCOPE_PREFIX) + r"(\w+)")
_INSTRUCTION = re.compile(r"%?([^\s=]+)")

# The fields read here of the profiler's XSpace (tsl/profiler/protobuf/
# xplane.proto) and of XLA's HloProto (xla/service/hlo.proto), by their
# field numbers; parsing skips every other field.
_SCHEMA = {
    "XSpace": [("planes", 1, "XPlane*")],
    "XPlane": [("name", 2, "string"), ("lines", 3, "XLine*"),
               ("event_metadata", 4, "EventMetadataEntry*")],
    # a map on the wire: repeated (key, value) entries
    "EventMetadataEntry": [("key", 1, "int64"),
                           ("value", 2, "XEventMetadata")],
    "XLine": [("name", 2, "string"), ("timestamp_ns", 3, "int64"),
              ("events", 4, "XEvent*")],
    "XEvent": [("metadata_id", 1, "int64"), ("offset_ps", 2, "int64"),
               ("duration_ps", 3, "int64")],
    "XEventMetadata": [("name", 2, "string"), ("stats", 5, "XStat*")],
    "XStat": [("bytes_value", 6, "bytes")],
    "HloProto": [("hlo_module", 1, "HloModuleProto")],
    "HloModuleProto": [("computations", 3, "HloComputationProto*")],
    "HloComputationProto": [("instructions", 2, "HloInstructionProto*")],
    "HloInstructionProto": [("name", 1, "bytes"), ("metadata", 7, "OpMetadata")],
    "OpMetadata": [("op_name", 2, "bytes")],
}


@functools.lru_cache(maxsize=None)
def _message(name: str):
    """The protobuf class of one ``_SCHEMA`` message."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    F = descriptor_pb2.FieldDescriptorProto
    scalar = {"string": F.TYPE_STRING, "bytes": F.TYPE_BYTES,
              "int64": F.TYPE_INT64}
    fd = descriptor_pb2.FileDescriptorProto(
        name="bench_trace_layers.proto", package="tl", syntax="proto3")
    for msg, fields in _SCHEMA.items():
        m = fd.message_type.add(name=msg)
        for fname, number, typ in fields:
            kind = typ.rstrip("*")
            f = m.field.add(name=fname, number=number, label=(
                F.LABEL_REPEATED if typ.endswith("*") else F.LABEL_OPTIONAL))
            if kind in scalar:
                f.type = scalar[kind]
            else:
                f.type, f.type_name = F.TYPE_MESSAGE, ".tl." + kind
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("tl." + name))


def op_names(hlo_proto: bytes) -> Dict[str, str]:
    """Instruction name -> ``op_name`` of one compiled module."""
    module = _message("HloProto").FromString(hlo_proto).hlo_module
    return {i.name.decode(): i.metadata.op_name.decode()
            for c in module.computations for i in c.instructions}


class Op(NamedTuple):
    plane: str
    start: float      # ns, on the trace's clock
    dur: float        # ns
    layer: str        # innermost dscep.<layer> of its op_name, "" if none

    @property
    def end(self) -> float:
        return self.start + self.dur


def scope_layer(op_name: str) -> str:
    """The innermost engine layer an ``op_name`` names, ``""`` if none."""
    for name in reversed(_SCOPE.findall(op_name)):
        if name in LAYERS:
            return name
    return ""


def load_xplane(path: str) -> Tuple[List[Op], List[TR.Ev]]:
    """The device ops, with their layers, and the ``dscep.*`` and
    ``bench.*`` host spans of an ``.xplane.pb``."""
    space = _message("XSpace")()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    modules = {}
    for plane in space.planes:
        if plane.name == METADATA_PLANE:
            modules = {e.value.name: e.value for e in plane.event_metadata}
    ops: List[Op] = []
    spans: List[TR.Ev] = []
    for plane in space.planes:
        meta = {e.key: e.value.name for e in plane.event_metadata}
        lines = {line.name: line for line in plane.lines}
        if TR.DEVICE_PLANE.match(plane.name) and TR.OPS_LINE in lines:
            ops += _device_ops(plane.name, meta, lines, modules)
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    name = meta[ev.metadata_id]
                    if name.startswith((SCOPE_PREFIX, "bench.")):
                        spans.append(TR.Ev(plane.name, line.name, name,
                                           *_times(line, ev)))
    return ops, spans


def _times(line, ev) -> Tuple[float, float]:
    """An event's start and duration, ns on the trace's clock."""
    return line.timestamp_ns + ev.offset_ps * 1e-3, ev.duration_ps * 1e-3


def _device_ops(plane: str, meta: Dict[int, str], lines, modules
                ) -> List[Op]:
    """One device's ops, each with the layer of its instruction in the
    program that ran it (the ``XLA Modules`` event enclosing it)."""
    runs = []                            # (start, end, program), by start
    if MODULES_LINE in lines:
        mods = lines[MODULES_LINE]
        for ev in mods.events:
            start, dur = _times(mods, ev)
            runs.append((start, start + dur, meta[ev.metadata_id]))
        runs.sort()
    starts = [r[0] for r in runs]
    names: Dict[str, Dict[str, str]] = {}
    layer_of: Dict[Tuple[str, int], str] = {}
    out = []
    line = lines[TR.OPS_LINE]
    for ev in line.events:
        start, dur = _times(line, ev)
        i = bisect.bisect_right(starts, start) - 1
        program = runs[i][2] if i >= 0 and start < runs[i][1] else ""
        layer = layer_of.get((program, ev.metadata_id))
        if layer is None:
            if program not in names:
                md = modules.get(program)
                names[program] = op_names(md.stats[0].bytes_value) \
                    if md is not None and md.stats else {}
            instr = _INSTRUCTION.match(meta[ev.metadata_id]).group(1)
            layer = layer_of[program, ev.metadata_id] = scope_layer(
                names[program].get(instr, ""))
        out.append(Op(plane, start, dur, layer))
    return out


def save(ops: Sequence[Op], spans: Sequence[TR.Ev], path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump({"ops": [list(o) for o in ops],
                   "spans": [list(s) for s in spans]}, f)


def load(path: str) -> Tuple[List[Op], List[TR.Ev]]:
    with gzip.open(path, "rt") as f:
        data = json.load(f)
    return ([Op(*o) for o in data["ops"]], [TR.Ev(*s) for s in data["spans"]])


def self_ns(ops: Iterable[Op], lo: float, hi: float) -> Dict[str, float]:
    """Busy ns of one device inside ``[lo, hi]`` by layer: each instant
    goes to the innermost op running then (the latest to start), an op
    without a layer takes its enclosing op's, else ``unscoped``."""
    evs = sorted((o for o in ops if o.end > lo and o.start < hi),
                 key=lambda o: (o.start, -o.dur))
    tot: Dict[str, float] = defaultdict(float)
    stack: List[Tuple[float, str]] = []      # (end, layer), innermost last
    t = lo

    def give(upto: float) -> None:
        nonlocal t
        if stack and upto > t:
            tot[stack[-1][1]] += upto - t
        t = max(t, upto)

    for o in evs:
        start, end = max(o.start, lo), min(o.end, hi)
        while stack and stack[-1][0] <= start:
            give(stack[-1][0])
            stack.pop()
        give(start)
        layer = o.layer or (stack[-1][1] if stack else UNSCOPED)
        stack.append((end, layer))
    while stack:
        give(stack[-1][0])
        stack.pop()
    return dict(tot)


def reduce(ops: Sequence[Op], spans: Sequence[TR.Ev]) -> Optional[dict]:
    """Layer seconds and named idle gaps of the busiest device in the
    traced window, or ``None`` when no device op ran in it."""
    lo, hi = TR.window(spans)
    per_dev: Dict[str, List[Op]] = defaultdict(list)
    for o in ops:
        if o.end > lo and o.start < hi:
            per_dev[o.plane].append(o)
    if not per_dev:
        return None
    busy = {p: TR.busy_ns(d, lo, hi) for p, d in per_dev.items()}
    busiest = max(busy, key=busy.get)
    layers = self_ns(per_dev[busiest], lo, hi)
    host = [s for s in spans if s.name != TR.WINDOW_SPAN]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busiest": busiest,
        "busy_s": busy[busiest] * 1e-9,
        "device_layers": [[k, v * 1e-9] for k, v in
                          sorted(layers.items(), key=lambda kv: -kv[1])],
        "idle_by_span": TR.idle_gaps(per_dev[busiest], host, lo, hi),
    }


if __name__ == "__main__":
    res = reduce(*load_xplane(sys.argv[1]))
    print(json.dumps(res, indent=1))
