"""One-shot jobs (thesis §3, Fig 1/4): :class:`Platform` runs one job and
returns a structured :class:`JobReport` (per-phase timings, queue-depth
trace, cache-proxy miss curve, straggler counts).

The one-shot front sits above both execution paths and adds none of its
own.  ``backend="threaded"`` runs the job as the only job of a
:class:`~repro.platform.service.PlatformService` opened for the call —
the same plan, arena, waves, scheduler, reduce tree and recovery as every
served job — and closes it.  ``backend="simulated"`` runs
:func:`~repro.platform.driver.run_virtual`, the virtual-time model.

The platform *configurations* of the evaluation (§4.1.3) select overhead
profiles:

  BTS  BashReduce + Task Sizing (kneepoint)        — the contribution
  BLT  BashReduce + Large Tasks (all samples/node)
  BTT  BashReduce + Tiniest Tasks (1 sample/task)
  VH   Vanilla-Hadoop-like: task-level monitoring + heavy startup + per-task
       launch overhead (JVM) + distributed-FS tax
  JLH  Job-level-Hadoop-like: monitoring off, startup reduced
  LH   Lite-Hadoop-like: no DFS interference (results "incorrect" in the
       thesis; kept for overhead benchmarking only)

Overhead constants are calibrated to the thesis' measurements (Fig 5/6:
vanilla Hadoop ≈ 4× BashReduce startup, ≈ 21% startup tax from monitoring,
≈ 20% per-task runtime tax, BashReduce ≈ 12% scheduling overhead).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core import kneepoint as kp
from repro.core import scheduler as sch
from repro.core import slo as slo_mod
from repro.platform import compute as pc
from repro.platform import telemetry as tel
from repro.platform.backend import SimulatedBackend
from repro.platform.driver import (
    CustomMap,
    MapFn,
    PlatformSpec,
    make_tasks,
    report_fields,
    resolve_platform_config,
    run_virtual,
    scheduler_config,
)
from repro.platform.monitor import PlatformMonitor, write_monitor_report
from repro.platform.service import PlatformService


@dataclasses.dataclass
class JobReport:
    """Structured job outcome — superset of the legacy tiny_task report."""

    platform: str
    n_tasks: int
    task_size_bytes: float
    makespan: float
    throughput_bps: float      # input bytes / second
    startup_time: float
    result: Optional[dict] = None
    kneepoint: Optional[kp.KneepointResult] = None
    # platform-driver extensions
    backend: str = "threaded"
    engine: str = "auto"
    phases: Dict[str, float] = dataclasses.field(default_factory=dict)
    # the scheduler's depth at each settle: the simulated two-phase
    # policy's dynamic look-ahead k; on real threads the tasks still
    # ready in the pool's queue
    queue_depths: List[int] = dataclasses.field(default_factory=list)
    miss_curve: Tuple[kp.CurvePoint, ...] = ()
    max_task_bytes: float = 0.0
    stragglers: int = 0
    speculative_launches: int = 0
    restarts: int = 0
    calibration_seconds: float = 0.0
    datastore_stats: Optional[Dict[str, float]] = None
    reduce_info: Optional[Dict[str, float]] = None
    # wave-execution observability (execute-phase map dispatches only;
    # warmup/kneepoint compiles are startup cost and are not counted)
    device_dispatches: int = 0
    bytes_uploaded: float = 0.0
    wave_sizes: List[int] = dataclasses.field(default_factory=list)
    # balanced-scheduling observability (DESIGN.md §9)
    speculation_wins: int = 0
    scale_decision: Optional[str] = None    # slo.choose_workers reasoning
    n_workers_used: int = 0
    prefetch_stats: Optional[Dict[str, float]] = None
    # worker-side block cache observability (DESIGN.md §14)
    cache_stats: Optional[Dict[str, float]] = None
    # error-bounded approximate execution (DESIGN.md §10)
    tasks_executed: int = 0
    tasks_cancelled: int = 0
    stop_reason: Optional[str] = None       # None ⇒ ran to completion
    final_ci: Optional[Dict[str, Any]] = None   # EstimateSnapshot dict
    # failure model / recovery observability (DESIGN.md §12)
    tasks_restored: int = 0        # leaves restored from a checkpoint
    checkpoint_saves: int = 0      # committed checkpoint steps this run
    fault_events: int = 0          # injected faults that fired this run


class Platform:
    """The one-shot driver.  ``datastore`` is an optional
    :class:`~repro.core.datastore.ReplicatedDataStore`; ``map_fn`` replaces
    the workload engine with a custom per-task callable
    ``(task, block, months, seed) -> partial`` (overhead benchmarks);
    ``fault_injector`` is an optional
    :class:`~repro.platform.faults.FaultInjector` driving a seeded
    :class:`~repro.platform.faults.FaultPlan` through the run
    (DESIGN.md §12)."""

    def __init__(self, spec: PlatformSpec = PlatformSpec(), *,
                 datastore=None, map_fn: Optional[MapFn] = None,
                 fault_injector=None):
        self.spec = spec
        self.datastore = datastore
        self.map_fn = map_fn
        self.fault_injector = fault_injector
        # one bus per driver, across its runs; the simulated backend
        # emits virtual timestamps, so its bus must not fall back to
        # wall time
        self.telemetry = tel.TelemetryBus(
            tel.resolve_telemetry_config(spec.telemetry),
            virtual=(spec.backend == "simulated"))
        # SLO monitor (DESIGN.md §15): a tap-driven bus consumer, built
        # only when enabled — the default leaves the bus untapped (zero
        # new events, zero threads, bit-identical results)
        self.monitor: Optional[PlatformMonitor] = None
        if spec.monitor.enabled:
            self.monitor = PlatformMonitor(self.telemetry, spec.monitor,
                                           wave_capacity=spec.max_wave)

    # -- the full data path --------------------------------------------------
    def run(self, samples: Dict[int, np.ndarray],
            months: Dict[int, np.ndarray], workload, *,
            resume_from: Optional[str] = None) -> JobReport:
        """Kneepoint → distribute → schedule/execute → streaming reduce.

        ``resume_from`` names a checkpoint directory written by a prior
        (interrupted) run of the same job: its committed leaf partials
        are restored into the reduce tree and only the missing tasks
        execute — bit-identical to an uninterrupted run (§12)."""
        if self.map_fn is not None:
            workload = CustomMap(self.map_fn)
        if self.spec.backend == "simulated":
            return JobReport(**run_virtual(
                self.spec, samples, months, workload,
                datastore=self.datastore,
                fault_injector=self.fault_injector,
                resume_from=resume_from, telemetry=self.telemetry))
        if self.spec.backend != "threaded":
            raise ValueError(f"unknown backend {self.spec.backend!r}")
        return self._run_served(samples, months, workload, resume_from)

    def _run_served(self, samples, months, workload,
                    resume_from: Optional[str]) -> JobReport:
        """The job as the only job of a service opened for this call."""
        spec, bus, store = self.spec, self.telemetry, self.datastore
        # the service shares this driver's bus; the driver's monitor
        # already taps it
        svc = PlatformService(dataclasses.replace(spec, monitor=None),
                              datastore=store,
                              fault_injector=self.fault_injector, _bus=bus)
        # every dispatch of this job (the service keeps a bounded window)
        # and the pool scheduler's depth at each settle
        dispatch, depths = pc.DispatchStats(), []
        bus.bind_dispatch(dispatch)
        bus.bind_depths(depths)
        # (monotonic time, exec seconds) of each settle: the execute
        # window's end, the straggler count, the data plane's feedback
        settles: List[Tuple[float, Optional[float]]] = []

        def on_settle(kind: str, ts: float, f: Dict[str, Any]) -> None:
            if kind == "task_settled":
                settles.append((time.monotonic(), f.get("exec_seconds")))

        bus.add_tap(on_settle)
        try:
            t0 = time.monotonic()
            handle = svc.register_dataset(samples, months)
            t_submit = time.monotonic()
            ticket = svc.submit(handle, workload,
                                checkpoint_dir=spec.checkpoint_dir,
                                resume_from=resume_from)
            result = ticket.result()
            pool = svc._pool
            prefetch_stats = (pool.prefetcher.stats()
                              if pool.prefetcher is not None else None)
        finally:
            bus.remove_tap(on_settle)
            svc.close()

        plan = ticket.plan
        execs = sorted(e for _, e in settles if e is not None)
        if store is not None:
            for e in execs:
                store.report_exec_time(e)
        median = execs[len(execs) // 2] if execs else 0.0
        t_last = settles[-1][0] if settles else ticket.admitted_at
        # the pool's start-up sleep and the execution, up to the reduce
        # tree's drain
        makespan = t_last - ticket.admitted_at
        plat = svc.plat
        cache = store.cache if store is not None else None
        return JobReport(
            platform=plat.name,
            n_tasks=ticket.n_tasks,
            task_size_bytes=(plan.knee_bytes if plan.knee_bytes is not None
                             else plan.total_bytes / max(ticket.n_tasks, 1)),
            makespan=makespan,
            throughput_bps=plan.total_bytes / max(makespan, 1e-12),
            startup_time=plat.startup_time,
            result=result,
            kneepoint=plan.knee_res,
            backend="threaded",
            engine=plan.engine,
            phases={
                "plan": plan.plan_seconds,
                "distribute": plan.partition_seconds + t_submit - t0,
                "compile": max(ticket.submitted_at - t_submit
                               - plan.plan_seconds
                               - plan.partition_seconds, 0.0),
                "execute": makespan,
                "reduce": ticket.finished_at - t_last},
            queue_depths=depths,
            miss_curve=(plan.knee_res.curve
                        if plan.knee_res is not None else ()),
            max_task_bytes=max((t.size_bytes for t in plan.tasks),
                               default=0.0),
            stragglers=sum(1 for e in execs if median and e > 2.0 * median),
            speculative_launches=pool.sched.speculative_launches,
            restarts=pool.worker_respawns,
            datastore_stats=store.stats() if store is not None else None,
            reduce_info=ticket.reduce_info,
            device_dispatches=dispatch.device_dispatches,
            bytes_uploaded=dispatch.bytes_uploaded,
            wave_sizes=list(dispatch.wave_sizes),
            speculation_wins=pool.sched.speculation_wins,
            scale_decision=svc.scale_decision,
            n_workers_used=pool.n_workers,
            prefetch_stats=prefetch_stats,
            cache_stats=cache.stats() if cache is not None else None,
            tasks_executed=ticket.tasks_executed,
            tasks_cancelled=ticket.tasks_cancelled,
            stop_reason=ticket.stop_reason,
            # only an error-bounded job reports its band
            final_ci=ticket.final_ci if ticket.epsilon is not None else None,
            tasks_restored=ticket.tasks_restored,
            checkpoint_saves=(ticket.checkpointer.saves
                              if ticket.checkpointer is not None else 0),
            fault_events=(len(self.fault_injector.fired)
                          if self.fault_injector is not None else 0))

    # -- virtual-time scale-out over a cost model ----------------------------
    def run_scaleout(self, sample_sizes: Sequence[int], *,
                     per_sample_exec: Optional[float] = None,
                     exec_model: Optional[Callable[[sch.Task], float]] = None,
                     fetch_model: Optional[Callable[[sch.Task], float]] = None,
                     ) -> JobReport:
        """Run the scheduling/distribution layers in virtual time over a
        calibrated cost model (datasets too large to materialize: Fig
        10-13 sweeps).  No statistics are computed (``result=None``)."""
        assert (per_sample_exec is None) != (exec_model is None), \
            "pass exactly one of per_sample_exec / exec_model"
        spec = self.spec
        plat = resolve_platform_config(spec)
        decision = None
        if (spec.slo_seconds is not None and per_sample_exec is not None
                and not spec.sim_workers and len(sample_sizes)):
            # SLO-aware sizing from the calibrated cost model (Fig 12/13)
            mean_bytes = float(np.mean(np.asarray(sample_sizes)))
            decision = slo_mod.choose_workers(
                max(spec.n_workers, 1),
                bytes_per_second_per_worker=(mean_bytes
                                             / float(per_sample_exec)),
                startup_seconds=plat.startup_time * spec.startup_scale,
                slo_seconds=spec.slo_seconds)
        if exec_model is None:
            rate = float(per_sample_exec)
            exec_model = lambda t: rate * len(t.sample_ids)   # noqa: E731
        n_eff = (decision.cores if decision is not None
                 else len(spec.sim_workers) if spec.sim_workers
                 else spec.n_workers)
        t0 = time.perf_counter()
        tasks = make_tasks(list(sample_sizes), plat.task_sizing,
                           spec.knee_bytes, n_eff)
        phases = {"plan": 0.0, "distribute": time.perf_counter() - t0,
                  "compile": 0.0}
        workers = (list(spec.sim_workers) if spec.sim_workers else n_eff)
        backend = SimulatedBackend(workers, exec_model=exec_model,
                                   fetch_model=fetch_model,
                                   startup_scale=spec.startup_scale)
        t0 = time.perf_counter()
        outcome = backend.run(tasks, compute=None, fetch=None, plat=plat,
                              cfg=scheduler_config(spec, plat),
                              emit=lambda tid, v: None)
        phases["execute"] = time.perf_counter() - t0
        phases["reduce"] = 0.0
        return JobReport(**report_fields(
            spec, plat, outcome, tasks, float(sum(sample_sizes)),
            spec.knee_bytes, None, "cost-model", phases, None, None,
            scale_decision=decision, n_workers_used=n_eff))

    # -- monitor surface (DESIGN.md §15) -------------------------------------
    def monitor_snapshot(self) -> Dict[str, Any]:
        """SLIs, alerts, per-job critical paths, and ranked findings —
        requires ``monitor=MonitorOptions(enabled=True)`` on the spec."""
        if self.monitor is None:
            raise RuntimeError(
                "monitor disabled; construct the Platform with "
                "PlatformSpec(monitor=MonitorOptions(enabled=True))")
        return self.monitor.snapshot()

    def write_monitor_report(self, path: str,
                             title: str = "platform monitor") -> None:
        """Self-contained HTML: alert timeline + per-job critical-path
        waterfall (requires the monitor to be enabled)."""
        if self.monitor is None:
            raise RuntimeError(
                "monitor disabled; construct the Platform with "
                "PlatformSpec(monitor=MonitorOptions(enabled=True))")
        write_monitor_report(self.monitor, path, title)
