"""Reduces a profiler trace (`.xplane.pb`) to what the per-layer metrics
read: the device's busy intervals inside the measured window, each
operation's device time, and the idle gaps labelled by what the host was
doing across them.

Device operations are the events of the line "XLA Ops" on the planes
named "/device:TPU:<n>". The window is delimited by the benchmark's own
host spans (`TraceAnnotation` "bench.poll"), which the profiler writes
on the same clock as the device events.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.poll"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    end_ns: float

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns


@dataclasses.dataclass
class Trace:
    device_ops: list          # [[Event]] one list per device
    host_events: list         # [Event] of the thread with the window spans


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.append([Event(e.name, e.start_ns,
                                          e.start_ns + e.duration_ns)
                                    for e in line.events])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events]
                if any(e.name == WINDOW_SPAN for e in evs):
                    host = evs
    return Trace(devices, host)


def window(host_events: list) -> tuple:
    """(start, end) of the measured window: the first window span's start
    to the last one's end."""
    spans = [e for e in host_events if e.name == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    return (min(e.start_ns for e in spans), max(e.end_ns for e in spans))


def clip(events: list, lo: float, hi: float) -> list:
    out = []
    for e in events:
        s, t = max(e.start_ns, lo), min(e.end_ns, hi)
        if t > s:
            out.append(Event(e.name, s, t))
    return out


def union(events: list) -> list:
    """Merged (start, end) intervals covered by any event."""
    merged: list = []
    for e in sorted(events, key=lambda e: e.start_ns):
        if merged and e.start_ns <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e.end_ns)
        else:
            merged.append([e.start_ns, e.end_ns])
    return [tuple(m) for m in merged]


def gaps(busy: list, lo: float, hi: float) -> list:
    """Idle (start, end) intervals of [lo, hi] outside `busy`."""
    out, cur = [], lo
    for s, t in busy:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, t)
    if hi > cur:
        out.append((cur, hi))
    return out


def op_seconds(events: list) -> dict:
    """Device seconds per operation name."""
    tot: dict = collections.defaultdict(float)
    for e in events:
        tot[e.name] += e.dur_ns / 1e9
    return dict(tot)


class Labeller:
    """Names the innermost host event open at a time. Events of one
    thread nest, so that is the latest-starting one still open."""

    def __init__(self, host_events: list):
        self.events = sorted(host_events, key=lambda e: e.start_ns)
        self.starts = [e.start_ns for e in self.events]

    def __call__(self, t: float) -> str:
        i = bisect.bisect_right(self.starts, t) - 1
        while i >= 0:
            if self.events[i].end_ns > t:
                return self.events[i].name
            i -= 1
        return "host idle"


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float              # mean over devices
    ops: dict                  # name -> device seconds (all devices)
    gap_labels: dict           # host label -> idle seconds (mean/device)

    def top_ops(self, n: int = 10) -> list:
        """The n operations with most device time, each named by its HLO
        instruction (the text before " = ")."""
        short: dict = collections.defaultdict(float)
        for k, v in self.ops.items():
            short[k.split(" = ")[0].lstrip("%")] += v
        return [[k, v] for k, v in sorted(short.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def top_gaps(self, n: int = 10) -> list:
        return [[k, v] for k, v in sorted(self.gap_labels.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def seconds_matching(self, match) -> float:
        """Device seconds of the operations whose name `match` accepts."""
        return sum(v for k, v in self.ops.items() if match(k))


def summarize(trace: Trace) -> Summary:
    lo, hi = window(trace.host_events)
    if not trace.device_ops:
        raise ValueError("the trace holds no device operations")
    ops: dict = collections.defaultdict(float)
    gap_labels: dict = collections.defaultdict(float)
    busy = 0.0
    n = len(trace.device_ops)
    label = Labeller(trace.host_events)
    for dev in trace.device_ops:
        inside = clip(dev, lo, hi)
        merged = union(inside)
        busy += sum(t - s for s, t in merged) / 1e9
        for k, v in op_seconds(inside).items():
            ops[k] += v
        for s, t in gaps(merged, lo, hi):
            gap_labels[label((s + t) / 2)] += \
                (t - s) / 1e9 / n
    return Summary((hi - lo) / 1e9, busy / n, dict(ops), dict(gap_labels))
