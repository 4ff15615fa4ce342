#!/usr/bin/env python3
"""The program's own host spans in a traced window, and the device idle
gaps they explain.

The service marks each stage of its served path with a host span named
``platform.*`` (DESIGN.md §13.7), recorded by ``jax.profiler`` on the
device trace's timeline.  ``tracing.read_xplane`` keeps only the
benchmark's ``bench.*`` spans, so the result line's breakdown gives every
idle gap to the client's ``bench.result_wait``.  This module reads both
prefixes, gives each gap to the program span that overlaps it most (to
the benchmark's span only where no program span overlaps it at all), and
sums each span name's seconds inside the window over every host thread.

    python3 bench/program_spans.py --workload <cell> --seed <n> --seconds <s>

runs one traced window of a cell as ``run.py --trace 1`` does, and prints
the breakdown, the top device operations and the window's job latencies
as the last line, a JSON object.
"""

from __future__ import annotations

import glob
import os
import shutil
import statistics
import tempfile
import time
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

T_START = time.perf_counter()

import numpy as np  # noqa: E402

PROGRAM_PREFIX = "platform."
NO_SPAN = "no span"


def _prefixes():
    from bench import tracing

    return (tracing.SPAN_PREFIX, PROGRAM_PREFIX)


def read_spans(path: str) -> List[Tuple[str, float, float]]:
    """Host events of an ``.xplane.pb`` named ``bench.*`` or
    ``platform.*``: ``(name, start_ns, duration_ns)``, every thread."""
    from jax.profiler import ProfileData

    keep = _prefixes()
    return [(e.name, e.start_ns, e.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(keep)]


def span_totals(spans: Sequence[Tuple[str, float, float]], lo: float,
                hi: float) -> Tuple[Dict[str, float], Dict[str, int]]:
    """Seconds of each span name inside ``[lo, hi)`` (ns), summed over
    threads, and how many spans of each name reach into it."""
    seconds: Dict[str, float] = defaultdict(float)
    counts: Dict[str, int] = defaultdict(int)
    for name, start, dur in spans:
        inside = min(start + dur, hi) - max(start, lo)
        if inside > 0:
            seconds[name] += inside * 1e-9
            counts[name] += 1
    return dict(seconds), dict(counts)


def _most_overlap(lo: float, hi: float, names, starts, ends) -> str:
    if not names:
        return NO_SPAN
    overlap = np.minimum(ends, hi) - np.maximum(starts, lo)
    best = int(np.argmax(overlap))
    return names[best] if overlap[best] > 0 else NO_SPAN


def place(gaps, spans: Sequence[Tuple[str, float, float]]) -> List[str]:
    """The span each gap goes to: the ``platform.*`` span that overlaps
    it most, else the ``bench.*`` span that does (the window's own span
    aside), else ``"no span"``."""
    from bench import tracing

    groups = []
    for program in (True, False):
        group = [(n, s, s + d) for n, s, d in spans
                 if n != tracing.WINDOW
                 and n.startswith(PROGRAM_PREFIX) == program]
        groups.append(([n for n, _, _ in group],
                       np.asarray([s for _, s, _ in group], np.float64),
                       np.asarray([e for _, _, e in group], np.float64)))
    names = []
    for lo, hi in gaps:
        name = _most_overlap(lo, hi, *groups[0])
        if name == NO_SPAN:
            name = _most_overlap(lo, hi, *groups[1])
        names.append(name)
    return names


def attribute(gaps, names: Sequence[str]) -> List[List]:
    """Each gap's seconds summed by the span it was placed in;
    ``[name, seconds]``, longest first."""
    total: Dict[str, float] = defaultdict(float)
    for (lo, hi), name in zip(gaps, names):
        total[name] += (hi - lo) * 1e-9
    return sorted(([n, s] for n, s in total.items()), key=lambda kv: -kv[1])


def breakdown(devices: Dict[str, list],
              spans: Sequence[Tuple[str, float, float]]) -> dict:
    """The window's idle gaps on its first device (as ``tracing.summarize``
    takes them), attributed; the share of idle time placed in program
    spans; and each span name's in-window seconds and count."""
    from bench import tracing

    windows = [(s, s + d) for n, s, d in spans if n == tracing.WINDOW]
    if not windows or not devices:
        return {}
    lo, hi = windows[0]
    events = devices[sorted(devices)[0]]
    busy = tracing.clip(tracing.union([(s, s + d) for _, s, d, _ in events]),
                        lo, hi)
    gaps = tracing.complement(busy, lo, hi)
    idle = attribute(gaps, place(gaps, spans))
    idle_s = sum(s for _, s in idle)
    program_s = sum(s for n, s in idle if n.startswith(PROGRAM_PREFIX))
    seconds, counts = span_totals(
        [sp for sp in spans if sp[0] != tracing.WINDOW], lo, hi)
    return {"window_s": (hi - lo) * 1e-9,
            "busy_s": sum(b - a for a, b in busy) * 1e-9,
            "idle_s": idle_s,
            "idle_in_program_spans": program_s / idle_s if idle_s else None,
            "idle_gaps": idle, "span_seconds": seconds,
            "span_counts": counts}


def ms_per_job(run, *names: str):
    """Milliseconds per window job that the jobs' tickets charge to the
    spans ``names`` (``JobTicket.span_seconds``); ``None`` without a
    trace, without jobs, or where no ticket holds any of the names (a
    program without those spans)."""
    if run.trace is None or not run.jobs:
        return None
    held = [getattr(j.ticket, "span_seconds", None) or {} for j in run.jobs]
    if not any(name in s for s in held for name in names):
        return None
    total = sum(s.get(name, 0.0) for s in held for name in names)
    return total / len(run.jobs) * 1e3


def _capture():
    """Start a trace the way ``tracing.capture`` does; returns the
    directory it writes to."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    where = tempfile.mkdtemp(prefix="bench-spans-")
    jax.profiler.start_trace(where, profiler_options=opts)
    return where


def main(argv=None) -> int:
    import argparse
    import json
    import pathlib
    import sys

    root = pathlib.Path(__file__).resolve().parent.parent
    sys.path[:0] = [str(root), str(root / "src")]
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    args = parser.parse_args(argv)

    import jax

    from bench import harness, tracing, traffic

    def say(text):
        print(f"# {text}", flush=True)

    s = harness.open_session(root, args.workload, seed=args.seed,
                             t_start=T_START, say=say)
    try:
        plan = traffic.plan(s.cell.traffic, args.seconds, args.seed)
        where = _capture()
        try:
            window = traffic.drive(s.service, s.handle, s.workload, plan,
                                   args.seconds)
        finally:
            jax.profiler.stop_trace()
    finally:
        s.close()
    try:
        path = glob.glob(os.path.join(where, "**", "*.xplane.pb"),
                         recursive=True)[0]
        devices = tracing.read_xplane(path).devices
        spans = read_spans(path)
        out = breakdown(devices, spans)
        # the benchmark's spans only: the operations need none, and
        # summarize's attribution is gaps x spans in pure Python
        ops = tracing.summarize(tracing.TraceData(
            devices, [sp for sp in spans
                      if sp[0].startswith(tracing.SPAN_PREFIX)]))
        if ops is not None:
            out["device_ops"] = ops.device_ops(6)
    finally:
        shutil.rmtree(where, ignore_errors=True)
    jobs = [j for j in window.jobs if j.result is not None]
    out.update(cell=args.workload, seed=args.seed, jobs=len(jobs))
    if jobs:
        out["job_p50_ms"] = statistics.median(j.latency_s * 1e3
                                              for j in jobs)
        out["input_gb_per_s"] = (s.dataset.nbytes * len(jobs)
                                 / window.seconds / 1e9)
        out["ms_per_job"] = {n: sec / len(jobs) * 1e3
                             for n, sec in out.get("span_seconds",
                                                   {}).items()}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
