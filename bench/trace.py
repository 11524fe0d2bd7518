"""Reduction of a profiler trace to the numbers the benchmark reports.

A traced run records the window with ``jax.profiler``; the harness puts a
host span ``bench.window`` around it and ``bench.<what>`` spans around
each call into a layer.  From the ``.xplane.pb``:

* busy time of each device: the union of the intervals of its XLA
  operations (line ``XLA Ops`` of a ``/device:TPU:<i>`` plane) inside the
  window;
* the idle time of the first device, cut at the edges of the host spans
  and each piece given to the innermost ``bench.*`` span covering it
  (``other`` where none does);
* time per operation (its HLO instruction name and result shape).

Device and host events of one trace share one clock in ``ProfileData``.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
_SHAPE = re.compile(r"\w+\[[\d,]*\]")

Interval = Tuple[float, float]


@dataclass
class TraceSummary:
    window_s: float
    busy_s: Dict[int, float]                    # device id -> seconds
    ops: Dict[str, float]                       # name -> seconds, all devices
    gaps: List[Tuple[str, float]] = field(default_factory=list)

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s.values()) / len(self.busy_s)


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: Sequence[Interval], lo: float,
         hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def op_name(text: str) -> str:
    """``%fusion.220 = (f32[2,2047]{...}, ...) fusion(...)`` ->
    ``fusion.220 f32[2,2047]``: the instruction and its (first) result
    shape.  Plain names stay as they are."""
    head, eq, rest = text.partition(" = ")
    if not eq:
        return text
    shape = _SHAPE.search(rest)
    name = head.lstrip("%")
    return f"{name} {shape.group(0)}" if shape else name


def label_gaps(gaps: Sequence[Interval],
               spans: Sequence[Tuple[str, float, float]]) -> Dict[str, float]:
    """Seconds of idle time per host span.  Each gap is cut at the edges
    of the spans inside it, and each piece goes to the shortest span
    covering it, or to ``other``."""
    out: Dict[str, float] = {}
    spans = sorted(spans, key=lambda sp: sp[1])
    starts = [sp[1] for sp in spans]
    longest = max((e - s for _, s, e in spans), default=0.0)
    for s, e in gaps:
        lo = bisect.bisect_left(starts, s - longest)
        near = [sp for sp in spans[lo:bisect.bisect_right(starts, e)]
                if sp[2] > s]
        cuts = sorted({s, e, *(x for _, a, b in near for x in (a, b)
                               if s < x < e)})
        for a, b in zip(cuts, cuts[1:]):
            mid = 0.5 * (a + b)
            cover = [(ee - ss, n) for n, ss, ee in near if ss <= mid <= ee]
            name = min(cover)[1][len(SPAN_PREFIX):] if cover else "other"
            out[name] = out.get(name, 0.0) + (b - a) * 1e-9
    return out


def reduce(path: str, devices: Optional[Sequence[int]] = None
           ) -> TraceSummary:
    """Read ``path`` (an ``.xplane.pb``) into a :class:`TraceSummary`.
    ``devices`` limits the planes read to those device ids."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    spans: List[Tuple[str, float, float]] = []
    ops_by_dev: Dict[int, List[Tuple[str, float, float]]] = {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            if devices is not None and dev not in devices:
                continue
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops_by_dev.setdefault(dev, []).extend(
                        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"{path}: no {WINDOW_SPAN} span")
    if not ops_by_dev:
        raise ValueError(f"{path}: no device operations")
    lo, hi = windows[0]
    busy, ops = {}, {}
    first_busy: List[Interval] = []
    for dev in sorted(ops_by_dev):
        evs = ops_by_dev[dev]
        inside = clip([(s, e) for _, s, e in evs], lo, hi)
        merged = union(inside)
        busy[dev] = sum(e - s for s, e in merged) * 1e-9
        if not first_busy:
            first_busy = merged
        for name, s, e in evs:
            c = clip([(s, e)], lo, hi)
            if c:
                op = op_name(name)
                ops[op] = ops.get(op, 0.0) + (c[0][1] - c[0][0]) * 1e-9
    gaps = []
    prev = lo
    for s, e in first_busy + [(hi, hi)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    by_label = label_gaps(gaps, [sp for sp in spans if sp[0] != WINDOW_SPAN])
    return TraceSummary(
        window_s=(hi - lo) * 1e-9, busy_s=busy,
        ops=ops, gaps=sorted(by_label.items(), key=lambda kv: -kv[1]))


def breakdown(summary: TraceSummary, n: int = 10) -> Dict:
    """The result line's ``breakdown``: the top device operations (seconds
    per chip) and the idle time of the first device by host span."""
    per_chip = len(summary.busy_s)
    top = sorted(summary.ops.items(), key=lambda kv: -kv[1])[:n]
    return {"device_ops": [[k, v / per_chip] for k, v in top],
            "idle_gaps": [[k, v] for k, v in summary.gaps[:n]]}
