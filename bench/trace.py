"""Reduction of a profiler trace (`.xplane.pb`) to the numbers the per-layer
metrics read: device busy time (the union of the intervals in which an
operation ran), time per device operation, kernel time by name, and the idle
gaps between operations, each attributed to the host span of the benchmark's
loop that it falls in.

Reads the trace with `jax.profiler.ProfileData` and nothing else. Device
planes are those named `/device:TPU:<n>`; their operations are the events of
the `XLA Ops` line. Host spans are the `bench.*` events the timed loop writes
with `jax.profiler.TraceAnnotation`. Host and device events share the
profiler's clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
SHAPE = re.compile(r"^\(?([a-z0-9]+\[[0-9,]*\])")


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Reduced:
    """A trace, reduced. Times in seconds; `window_s` is the traced window
    (first loop span's start to last loop span's end)."""
    window_s: float
    busy_s: float                       # mean over the device planes
    n_devices: int
    op_time_s: Dict[str, float]         # device 0: total self time per op name
    op_calls: Dict[str, int]
    idle_by_span: Dict[str, float]      # device 0: idle time by host span

    def kernel_time(self, pattern: str) -> Tuple[float, int]:
        """(seconds, calls) of the device-0 ops whose name matches."""
        rx = re.compile(pattern)
        names = [n for n in self.op_time_s if rx.search(n)]
        return (sum(self.op_time_s[n] for n in names),
                sum(self.op_calls[n] for n in names))

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_time_s.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> Tuple[Dict[int, List[Event]], List[Event]]:
    """(device index -> its ops, host `bench.*` spans) from an xplane file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[int, List[Event]] = {}
    spans: List[Event] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                devices.setdefault(int(m.group(1)), []).extend(
                    Event(op_name(e.name), e.start_ns, e.duration_ns)
                    for e in line.events)
            elif plane.name.startswith("/host:"):
                spans.extend(Event(e.name, e.start_ns, e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return devices, spans


def op_name(text: str) -> str:
    """The HLO instruction's name and the shape of its (first) output, from
    the op's text in the trace: `%copy.3 = s8[512,64]{1,0} copy(…)` ->
    `copy.3 s8[512,64]`."""
    name, _, rest = text.partition(" = ")
    m = SHAPE.match(rest)
    return name.lstrip("%") + (f" {m.group(1)}" if m else "")


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge overlapping (start, end) intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def reduce(devices: Dict[int, List[Event]], spans: List[Event]) -> Reduced:
    if not devices:
        raise ValueError("the trace holds no TPU device plane with XLA ops")
    if not spans:
        raise ValueError("the trace holds no bench.* host span")
    lo = min(s.start_ns for s in spans)
    hi = max(s.end_ns for s in spans)
    busy = []
    for evs in devices.values():
        merged = clip(union([(e.start_ns, e.end_ns) for e in evs]), lo, hi)
        busy.append(sum(e - s for s, e in merged))
    dev0 = devices[min(devices)]
    op_time: Dict[str, float] = defaultdict(float)
    op_calls: Dict[str, int] = defaultdict(int)
    for e, own in self_times(dev0):
        if e.end_ns > lo and e.start_ns < hi:
            op_time[e.name] += own * 1e-9
            op_calls[e.name] += 1
    merged = clip(union([(e.start_ns, e.end_ns) for e in dev0]), lo, hi)
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    idle: Dict[str, float] = defaultdict(float)
    for s, e in gaps:
        idle[_span_at(spans, (s + e) / 2)] += (e - s) * 1e-9
    return Reduced(window_s=(hi - lo) * 1e-9,
                   busy_s=sum(busy) / len(busy) * 1e-9,
                   n_devices=len(devices), op_time_s=dict(op_time),
                   op_calls=dict(op_calls), idle_by_span=dict(idle))


def self_times(events: List[Event]) -> List[Tuple[Event, float]]:
    """Each op with its self time: its duration less that of the ops nested
    inside it (a `while` or `conditional` op spans the ops of its body)."""
    out: List[List] = []
    stack: List[List] = []
    for e in sorted(events, key=lambda e: (e.start_ns, -e.dur_ns)):
        while stack and stack[-1][0].end_ns <= e.start_ns:
            stack.pop()
        item = [e, e.dur_ns]
        if stack:
            stack[-1][1] -= e.dur_ns
        stack.append(item)
        out.append(item)
    return [(e, max(own, 0.0)) for e, own in out]


def _span_at(spans: List[Event], t: float) -> str:
    """The innermost `bench.*` span open at time t, or "host" when none."""
    best: Optional[Event] = None
    for s in spans:
        if s.start_ns <= t < s.end_ns and (best is None or s.dur_ns < best.dur_ns):
            best = s
    return best.name if best else "host"


def reduce_file(path: str) -> Reduced:
    return reduce(*load(path))
