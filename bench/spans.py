"""The program's own profiler spans inside the measured window, with their
statistics: the `serve.*` spans that `InferenceServer.poll` and the
session's engine open on the serving thread (docs/serving.md, "Tracing a
served batch"). `trace_reduce.load` keeps each event's name and times
only; the readers of these spans need the statistics too.

The spans are those of the host line that holds the window spans
(`bench.poll`), clipped to the window: the first `bench.poll` to the
last. A run without a trace, or a program that opens no such span, gives
none, and each reader then reports nothing.
"""
from __future__ import annotations

import dataclasses
import functools
import os

from bench import trace_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_DIR = os.path.join(ROOT, ".bench", "trace")
BATCH = "serve.batch"


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start_ns: float
    end_ns: float
    stats: dict = dataclasses.field(default_factory=dict)

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns


def clip(spans: list, lo: float, hi: float) -> list:
    """The spans that overlap [lo, hi], cut to it, statistics kept."""
    return [dataclasses.replace(s, start_ns=max(s.start_ns, lo),
                                end_ns=min(s.end_ns, hi))
            for s in spans if min(s.end_ns, hi) > max(s.start_ns, lo)]


def load(path: str) -> list:
    """The window's spans of the trace at `path`."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = [Span(e.name, e.start_ns, e.start_ns + e.duration_ns,
                          dict(e.stats)) for e in line.events]
            if any(s.name == trace_reduce.WINDOW_SPAN for s in spans):
                return clip(spans, *trace_reduce.window(spans))
    return []


@functools.lru_cache(maxsize=1)
def _load_once(path: str, mtime_ns: int) -> tuple:
    return tuple(load(path))


def of_run(run) -> list:
    """The window's spans of the run's trace; none without a trace."""
    if run.summary is None:
        return []
    path = trace_reduce.find_xplane(TRACE_DIR)
    return list(_load_once(path, os.stat(path).st_mtime_ns))


def named(spans: list, name: str) -> list:
    return [s for s in spans if s.name == name]


def ms_per_batch(spans: list, name: str):
    """Milliseconds of the spans called `name` per `serve.batch` span;
    None where either is missing."""
    batches, found = named(spans, BATCH), named(spans, name)
    if not batches or not found:
        return None
    return sum(s.dur_ns for s in found) / 1e6 / len(batches)
