"""Load a cell by name and run it: set-up, a measured window of served
jobs, the comparison with the plain reference, and the result line.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric lives in a file of its own, found by the name that
``BENCHMARK.json`` gives it:

* ``configs/<config>.json``   the deployment (the ``file`` of its entry);
* ``traffic/<traffic>.json``  the query and the arrivals;
* ``workloads/<cell>.json``   the comparison's limits and sample size;
* ``metrics/<metric>.py``     a ``read(run)`` that returns the number,
  or ``None`` where the run holds nothing to read.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import pathlib
import statistics
import sys
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import data, reference, roofline, tracing, traffic

class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    checks: dict
    end_to_end: Dict[str, str]        # metric name -> unit
    per_layer: Dict[str, str]
    root: pathlib.Path


def _for_cell(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(root: pathlib.Path, name: str,
              overrides: Optional[dict] = None) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json``, with its files read.
    ``overrides`` replaces keys of the configuration (tests shrink the
    data set with it)."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r}; cells: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    config.update(overrides or {})
    bench_dir = root / bench["paths"][0]
    mix = json.loads((bench_dir / "traffic" / f"{cell['traffic']}.json")
                     .read_text())
    checks = json.loads((bench_dir / "workloads" / f"{name}.json")
                        .read_text())
    return Cell(
        name=name, chips=cell["chips"], config=config, traffic=mix,
        checks=checks,
        end_to_end={m["name"]: m["unit"] for m in bench["end_to_end"]
                    if _for_cell(m, name)},
        per_layer={m["name"]: m["unit"] for m in bench["per_layer"]
                   if _for_cell(m, name)},
        root=bench_dir)


def load_reader(bench_dir: pathlib.Path, metric: str) -> Callable:
    path = bench_dir / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def find_devices(chips: int, require_chip: bool):
    import jax

    devices = jax.devices()
    if require_chip and devices[0].platform == "cpu":
        raise NoChip("JAX found no accelerator (platform 'cpu')")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices


def program_workload(query: dict):
    """The program's workload object for a traffic file's query."""
    if query["statistic"] == "moments":
        from repro.platform.compute import MomentsSpec

        return MomentsSpec(name=query["name"], draws=query["draws"],
                           draw_size=query["draw_size"])
    from repro.core.subsample import SubsampleWorkload

    return SubsampleWorkload(query["name"], query["statistic"],
                             draws=query["draws"],
                             draw_size=query["draw_size"],
                             grid=query["grid"])


class CompileCounter:
    """Backend compiles seen by this process since it was made."""

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event.endswith("backend_compile_duration"):
            self.count += 1


@dataclasses.dataclass
class Run:
    """What a metric reader reads: the window's finished jobs, set-up
    timings, the trace's summary (``None`` without ``--trace 1``), the
    data set and the query, and the chip's peaks (``None`` off the chip)."""

    jobs: List[traffic.Job]
    window_s: float
    first_query_s: float
    setup_s: float
    dataset: data.Dataset
    knee_bytes: float
    query: dict
    peaks: Optional[dict]
    trace: Optional[tracing.TraceSummary] = None
    notes: List[str] = dataclasses.field(default_factory=list)

    def note(self, text: str) -> None:
        self.notes.append(text)


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def end_to_end(run: Run, peak_bytes: int) -> Dict[str, float]:
    lat = [j.latency_s * 1e3 for j in run.jobs]
    out = {"setup_s": run.setup_s, "peak_hbm_gb": peak_bytes / 1e9}
    if lat:
        out["job_p50_ms"] = statistics.median(lat)
    if run.window_s > 0:
        out["input_gb_per_s"] = (run.dataset.nbytes * len(run.jobs)
                                 / run.window_s / 1e9)
    return out


def compare(cell: Cell, ds: data.Dataset, jobs: List[traffic.Job],
            seed: int, knee_bytes: float, say) -> Dict[str, dict]:
    """The widest gap of each output over a sample of the window's jobs
    (drawn from ``seed``), each beside its limit."""
    limits = cell.checks["limits"]
    done = [j for j in jobs if j.result is not None]
    picks = np.random.default_rng(seed).permutation(len(done))
    sample = [done[i] for i in sorted(picks[:cell.checks["sample_jobs"]])]
    worst = {key: 0.0 for key in limits}
    for job in sample:
        want = reference.job_result(ds, knee_bytes, job.seed,
                                    cell.traffic["query"])
        for key, gap in reference.gaps(job.result, want).items():
            worst[key] = max(worst.get(key, 0.0), gap)
    if not sample:
        worst = {key: reference.NEVER for key in limits}
    say(f"compared {len(sample)} of {len(done)} finished jobs "
        f"(job seeds {[j.seed for j in sample]}) with the float64 reference")
    return {key: {"value": worst[key], "limit": limits[key]}
            for key in limits}


@dataclasses.dataclass
class Session:
    """A cell set up to serve: its data set registered with one service,
    and the first query (plan, pack, upload, compile) done."""

    cell: Cell
    devices: list
    peaks: Optional[dict]
    dataset: data.Dataset
    knee_bytes: float
    workload: object
    service: object
    handle: object
    first_query_s: float
    setup_s: float
    compiles: CompileCounter

    def close(self) -> None:
        self.service.close()
        self.service = self.handle = None
        gc.collect()


def open_session(root: pathlib.Path, name: str, *, seed: int,
                 t_start: float, require_chip: bool = True,
                 overrides: Optional[dict] = None,
                 say: Callable[[str], None] = print) -> Session:
    """Find the chips, make the data set from ``seed``, build one
    ``PlatformService`` (the default spec, the configuration's knee
    pinned), register the data set and run one job of the cell's query.
    ``t_start`` is the process's start on the ``perf_counter`` clock, so
    that set-up counts JAX's own start."""
    import jax

    from repro.platform import PlatformService, PlatformSpec
    from repro.platform.compile_cache import enable_compile_cache

    cell = load_cell(root, name, overrides)
    devices = find_devices(cell.chips, require_chip)
    kind = devices[0].device_kind
    peaks = roofline.peaks(kind) if require_chip else None
    say(f"device: platform {devices[0].platform}, kind {kind!r}, "
        f"count {len(devices)}")
    if require_chip:
        # every program, however quick to compile, so that only a cell's
        # first run in a checkout compiles
        say(f"compile cache: {enable_compile_cache(root / '.jax_cache')}")
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compiles = CompileCounter()

    cfg = cell.config
    t0 = time.perf_counter()
    ds = data.generate(cfg, seed)
    say(f"data {cfg['name']}: {len(ds.lengths)} samples, "
        f"{len(ds.values)} values, {ds.nbytes} bytes, longest "
        f"{int(ds.lengths.max())}; made in {time.perf_counter() - t0:.3f} s")
    knee = float(cfg["knee_bytes"])
    wl = program_workload(cell.traffic["query"])
    svc = PlatformService(PlatformSpec(knee_bytes=knee))
    try:
        handle = svc.register_dataset(ds.samples, ds.months,
                                      name=cfg["name"], knee_bytes=knee)
        t0 = time.perf_counter()
        warm = svc.submit(handle, wl, seed=0)
        warm.result()
    except BaseException:
        svc.close()
        raise
    first_query_s = time.perf_counter() - t0
    say(f"first query: {warm.n_tasks} tasks, {warm.device_dispatches} "
        f"dispatches, {warm.bytes_uploaded:.0f} bytes uploaded, "
        f"{first_query_s:.3f} s, {compiles.count} compiles so far")
    return Session(cell, devices, peaks, ds, knee, wl, svc, handle,
                   first_query_s, time.perf_counter() - t_start, compiles)


def peak_bytes() -> int:
    """``peak_bytes_in_use`` of the fullest local device."""
    import jax

    return int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in jax.local_devices()))


def run_cell(root: pathlib.Path, name: str, *, seed: int, seconds: float,
             trace: bool, t_start: float, require_chip: bool = True,
             overrides: Optional[dict] = None,
             say: Callable[[str], None] = print) -> dict:
    """One run of cell ``name``; returns the result line's object."""
    s = open_session(root, name, seed=seed, t_start=t_start,
                     require_chip=require_chip, overrides=overrides, say=say)
    cell, ds = s.cell, s.dataset
    try:
        plan = traffic.plan(cell.traffic, seconds, seed)
        c0 = s.compiles.count
        with tracing.capture(trace) as box:
            window = traffic.drive(s.service, s.handle, s.workload, plan,
                                   seconds)
        in_window = s.compiles.count - c0
        peak = peak_bytes()
    finally:
        s.close()

    jobs = [j for j in window.jobs if j.result is not None]
    failed = len(window.jobs) - len(jobs)
    say(f"window: {len(window.jobs)} jobs attempted, {failed} failed, "
        f"{window.seconds:.3f} s; {in_window} compiles inside the window")
    if window.lateness:
        late = np.asarray(window.lateness) * 1e3
        say(f"open-loop generator lateness: median {np.median(late):.3f} ms,"
            f" p95 {percentile(late, 95):.3f} ms, max {late.max():.3f} ms")
    run = Run(jobs=jobs, window_s=window.seconds,
              first_query_s=s.first_query_s, setup_s=s.setup_s, dataset=ds,
              knee_bytes=s.knee_bytes, query=cell.traffic["query"],
              peaks=s.peaks)
    dev = s.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(s.devices), "memory_peak_bytes": peak}
    result = {"correct": False, "attempted": len(window.jobs),
              "failed": failed}
    if trace:
        run.trace = tracing.summarize(box[0]) if box[0] is not None else None
        metrics = {}
        for metric, unit in cell.per_layer.items():
            value = load_reader(cell.root, metric)(run)
            if value is not None:
                metrics[metric] = {"value": value, "unit": unit}
        if run.trace is not None:
            device.update(busy_s=run.trace.busy_s,
                          window_s=run.trace.window_s)
            result["breakdown"] = {"device_ops": run.trace.device_ops(),
                                   "idle_gaps": run.trace.idle_gaps}
    else:
        values = end_to_end(run, peak)
        metrics = {m: {"value": values[m], "unit": unit}
                   for m, unit in cell.end_to_end.items() if m in values}
    for text in run.notes:
        say(text)
    checks = compare(cell, ds, window.jobs, seed, s.knee_bytes, say)
    result.update(
        correct=failed == 0 and all(c["value"] <= c["limit"]
                                    for c in checks.values()),
        metrics=metrics, device=device)
    result["check"] = checks
    return result


def print_checks(checks: Dict[str, dict], stream=sys.stderr) -> None:
    for key, c in checks.items():
        print(f"check {key}: {c['value']!r} limit {c['limit']!r}",
              file=stream, flush=True)
