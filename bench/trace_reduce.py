"""Reduction of a profiler trace to the benchmark's device numbers.

A trace is read into flat events ``(plane, line, name, start_ns,
dur_ns)``.  Device planes are ``/device:TPU:<n>``; their op line
(``XLA Ops``) holds one event per device operation, named by its HLO
instruction text (shapes included).  The benchmark's own
host spans (``bench.*``, written by ``jax.profiler.TraceAnnotation`` on the
host plane) give the measured window and what the host was doing.

* busy time: the union of a device's op intervals inside the window;
* kernel time: the summed durations of the ops a kernel name identifies;
* idle gaps: the stretches of the window no op covers, each named by the
  innermost ``bench.*`` span open at its midpoint.
"""
from __future__ import annotations

import bisect
import gzip
import json
import re
from collections import defaultdict
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
WINDOW_SPAN = "bench.window"
MAX_SPAN_NS = 600e9      # no bench span outlasts a run


class Ev(NamedTuple):
    plane: str
    line: str
    name: str
    start: float      # ns, on the trace's clock
    dur: float        # ns

    @property
    def end(self) -> float:
        return self.start + self.dur


def load_xplane(path: str) -> List[Ev]:
    """The device ops and the benchmark's host spans of an ``.xplane.pb``."""
    from jax.profiler import ProfileData

    out = []
    names: Dict[str, str] = {}       # one string per distinct op name
    for plane in ProfileData.from_file(path).planes:
        device = DEVICE_PLANE.match(plane.name)
        if not device and not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                name = names.setdefault(ev.name, ev.name)
                if device or name.startswith("bench."):
                    out.append(Ev(plane.name, line.name, name,
                                  float(ev.start_ns), float(ev.duration_ns)))
    return out


def save(events: Sequence[Ev], path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump([list(e) for e in events], f)


def load(path: str) -> List[Ev]:
    with gzip.open(path, "rt") as f:
        return [Ev(*e) for e in json.load(f)]


def window(events: Iterable[Ev]) -> Tuple[float, float]:
    spans = [e for e in events if e.name == WINDOW_SPAN]
    if not spans:
        raise ValueError("the trace holds no %r span" % WINDOW_SPAN)
    w = max(spans, key=lambda e: e.dur)
    return w.start, w.end


def device_ops(events: Iterable[Ev]) -> Dict[str, List[Ev]]:
    """``plane -> op events`` for every device plane that ran an op."""
    out: Dict[str, List[Ev]] = defaultdict(list)
    for e in events:
        if e.line == OPS_LINE and DEVICE_PLANE.match(e.plane):
            out[e.plane].append(e)
    return dict(out)


def merged(intervals: Iterable[Tuple[float, float]], lo: float, hi: float
           ) -> List[Tuple[float, float]]:
    """The union of intervals, clipped to ``[lo, hi]``, sorted."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(ops: Sequence[Ev], lo: float, hi: float) -> float:
    return sum(b - a for a, b in merged(((e.start, e.end) for e in ops), lo, hi))


def matches(e: Ev, kernel: str) -> bool:
    """An op is the kernel's when its HLO text names it."""
    return kernel in e.name


_LAYOUT = re.compile(r"\{[^{}]*\}")


def hlo_call(text: str) -> str:
    """An op's HLO instruction up to the end of its operand list (the
    profiler names device ops by their HLO text; what follows the operands,
    such as layout constraints, repeats shapes and is cut)."""
    for mark in (", custom_call_target=", ", kind=", ", calls=",
                 ", condition=", ", metadata="):
        cut = text.find(mark)
        if cut >= 0:
            text = text[:cut]
    return text


def short_name(text: str, width: int = 120) -> str:
    """An op's HLO instruction without layouts, cut to ``width``."""
    return _LAYOUT.sub("", hlo_call(text)).lstrip("%")[:width]


def op_ns(ops: Sequence[Ev], lo: float, hi: float
          ) -> Tuple[Dict[str, float], Dict[str, int]]:
    """Device ns and calls inside ``[lo, hi]`` per op name (one HLO
    instruction)."""
    tot: Dict[str, float] = defaultdict(float)
    calls: Dict[str, int] = defaultdict(int)
    for e in ops:
        if e.end > lo and e.start < hi:
            tot[e.name] += min(e.end, hi) - max(e.start, lo)
            calls[e.name] += 1
    return tot, calls


def kernel_ns(per_op: Dict[str, float], kernel: str) -> float:
    """Device ns of the ops whose HLO text names ``kernel``."""
    return sum(ns for name, ns in per_op.items() if kernel in name)


def top_ops(per_op: Dict[str, float], n: int = 10) -> List[List]:
    """The ``n`` ops with the most device time, ``[[name, s], ...]``."""
    tot: Dict[str, float] = defaultdict(float)
    for name, ns in per_op.items():
        tot[short_name(name)] += ns
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in best]


def host_spans(events: Iterable[Ev]) -> List[Ev]:
    return [e for e in events
            if e.plane.startswith("/host") and e.name.startswith("bench.")
            and e.name != WINDOW_SPAN]


def idle_gaps(ops: Sequence[Ev], spans: Sequence[Ev], lo: float, hi: float,
              n: int = 10) -> List[List]:
    """Idle time of the window by the host span open during it,
    ``[[span, s], ...]`` largest first (``host:none`` where no span was)."""
    busy = merged(((e.start, e.end) for e in ops), lo, hi)
    gaps, cur = [], lo
    for a, b in busy:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if cur < hi:
        gaps.append((cur, hi))
    spans = sorted(spans, key=lambda e: e.start)
    starts = [s.start for s in spans]
    tot: Dict[str, float] = defaultdict(float)
    for a, b in gaps:
        mid = 0.5 * (a + b)
        name = "none"
        # the innermost open span is the latest-starting one that covers mid
        for i in range(bisect.bisect_right(starts, mid) - 1, -1, -1):
            if spans[i].end >= mid:
                name = spans[i].name
                break
            if mid - spans[i].start > MAX_SPAN_NS:
                break
        tot["host:" + name] += b - a
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in best]


def reduce(events: Sequence[Ev], kernels: Sequence[str] = ()) -> Optional[dict]:
    """The device numbers of one traced window, or ``None`` when no device
    op ran in it.  Per device: busy seconds, kernel seconds and the calls
    of each kernel op; for the busiest device: its top ops and idle gaps."""
    lo, hi = window(events)
    per_dev = device_ops(events)
    devs = {p: ops for p, ops in per_dev.items()
            if any(e.end > lo and e.start < hi for e in ops)}
    if not devs:
        return None
    busy = {p: busy_ns(ops, lo, hi) * 1e-9 for p, ops in devs.items()}
    busiest = max(busy, key=busy.get)
    per_op, calls = {}, {}
    for p, ops in devs.items():
        per_op[p], calls[p] = op_ns(ops, lo, hi)
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy,
        "busiest": busiest,
        "kernel_s": {p: {k: kernel_ns(per_op[p], k) * 1e-9 for k in kernels}
                     for p in devs},
        "kernel_calls": {p: {name: n for name, n in calls[p].items()
                             if any(k in name for k in kernels)}
                         for p in devs},
        "device_ops": top_ops(per_op[busiest]),
        "idle_gaps": idle_gaps(devs[busiest], host_spans(events), lo, hi),
    }
