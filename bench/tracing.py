"""The profiler trace of a run's window, and its reduction to numbers.

``capture`` records a window with ``jax.profiler`` and keeps two things:
each device's operations (name, start, duration, and the strings of the
event's stats) and the benchmark's own host spans (``bench.*``, written
with ``jax.profiler.TraceAnnotation``).  ``summarize`` turns those into
the window's length, the device's busy time (the union of its operation
intervals), the operations that took most time, and the idle gaps
attributed to the span the host was in.  The reduction is plain Python
over plain tuples, so a test can feed it a small recorded trace.
"""

from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import shutil
import tempfile
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW = "bench.window"
SPAN_PREFIX = "bench."
# device lines that hold one event per executed operation
OP_LINES = ("XLA Ops",)
NAME_CHARS = 100

Event = Tuple[str, float, float, str]     # name, start_ns, duration_ns, detail
Span = Tuple[str, float, float]           # name, start_ns, duration_ns


@dataclasses.dataclass
class TraceData:
    devices: Dict[str, List[Event]]
    spans: List[Span]


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                      # mean over the devices traced
    op_seconds: Dict[str, float]       # device time per operation name
    op_detail: Dict[str, str]          # the stats' strings of each name
    idle_gaps: List[Tuple[str, float]]  # gap seconds per host span

    def seconds_of(self, pattern: str) -> float:
        """Device seconds of the operations whose name or stats name
        ``pattern``."""
        return sum(s for name, s in self.op_seconds.items()
                   if pattern in name or pattern in self.op_detail[name])

    def device_ops(self, top: int = 10) -> List[Tuple[str, float]]:
        """The operations that took most device time; a name is the
        start of the operation's HLO text (its name and shape)."""
        ranked = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])
        return [[name[:NAME_CHARS], s] for name, s in ranked[:top]]


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge ``(start, end)`` intervals into disjoint, sorted ones."""
    out: List[List[float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def complement(busy, lo: float, hi: float) -> List[Tuple[float, float]]:
    gaps, at = [], lo
    for a, b in busy:
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if hi > at:
        gaps.append((at, hi))
    return gaps


def attribute(gaps, spans: Sequence[Span]) -> List[Tuple[str, float]]:
    """Each gap's seconds go to the host span that overlaps it most (or
    to ``"no span"``); returns ``[name, seconds]``, longest first."""
    total: Dict[str, float] = defaultdict(float)
    spans = [(n, s, s + d) for n, s, d in spans if n != WINDOW]
    for lo, hi in gaps:
        best, best_overlap = "no span", 0.0
        for name, a, b in spans:
            overlap = min(b, hi) - max(a, lo)
            if overlap > best_overlap:
                best, best_overlap = name, overlap
        total[best] += (hi - lo) * 1e-9
    return sorted(([n, s] for n, s in total.items()), key=lambda kv: -kv[1])


def summarize(trace: TraceData) -> Optional[TraceSummary]:
    windows = [(s, s + d) for n, s, d in trace.spans if n == WINDOW]
    if not windows or not trace.devices:
        return None
    lo, hi = windows[0]
    busy_total, op_seconds, op_detail = 0.0, defaultdict(float), {}
    first_busy = None
    for plane in sorted(trace.devices):
        events = trace.devices[plane]
        busy = clip(union([(s, s + d) for _, s, d, _ in events]), lo, hi)
        busy_total += sum(b - a for a, b in busy)
        if first_busy is None:
            first_busy = busy
        for name, s, d, detail in events:
            inside = min(s + d, hi) - max(s, lo)
            if inside > 0:
                op_seconds[name] += inside * 1e-9
                op_detail.setdefault(name, detail)
    gaps = complement(first_busy, lo, hi)
    return TraceSummary(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy_total * 1e-9 / len(trace.devices),
        op_seconds=dict(op_seconds), op_detail=op_detail,
        idle_gaps=attribute(gaps, trace.spans)[:10])


def _detail(event) -> str:
    return " ".join(str(v) for _, v in event.stats if isinstance(v, str))


def read_xplane(path: str) -> TraceData:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    spans: List[Span] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            events = [(e.name, e.start_ns, e.duration_ns, _detail(e))
                      for line in plane.lines if line.name in OP_LINES
                      for e in line.events]
            if events:
                devices[plane.name] = events
        elif plane.name.startswith("/host:"):
            spans.extend((e.name, e.start_ns, e.duration_ns)
                         for line in plane.lines for e in line.events
                         if e.name.startswith(SPAN_PREFIX))
    return TraceData(devices, spans)


@contextlib.contextmanager
def capture(enabled: bool):
    """Trace the body when ``enabled``; yields a one-element list that
    holds the :class:`TraceData` once the body has ended."""
    box: List[Optional[TraceData]] = [None]
    if not enabled:
        yield box
        return
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    where = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        jax.profiler.start_trace(where, profiler_options=opts)
        try:
            yield box
        finally:
            jax.profiler.stop_trace()
        found = glob.glob(os.path.join(where, "**", "*.xplane.pb"),
                          recursive=True)
        if found:
            box[0] = read_xplane(found[0])
    finally:
        shutil.rmtree(where, ignore_errors=True)
