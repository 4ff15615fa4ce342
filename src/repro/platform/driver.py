"""The platform's job substrate (thesis §3, Fig 1/4): what every job is
configured and planned with, whichever clock it runs on.

The pieces the thesis argues only win *together*:

  kneepoint task sizing (§3.2)  →  datastore distribution (§3.5)
      →  dynamic scheduling (§3.4)  →  streaming reduce (§3.1)

This module holds the configuration (:class:`PlatformSpec` and its
option groups), the plan phase (:func:`plan_job`: the kneepoint, the task
partition, the block shapes), the device-resident wave state
(:func:`build_wave_context`), the checkpoint of reduce partials
(:class:`JobCheckpointer`), and the mode resolvers.  Real-time jobs run
through :class:`~repro.platform.service.PlatformService`; virtual-time
jobs run through :func:`run_virtual`, the discrete-event model of the
thesis' scale-out studies.  :class:`~repro.platform.facade.Platform` is
the one-shot front for both.

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
import threading
import time
import warnings
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.core import estimator as est_mod
from repro.core import kneepoint as kp
from repro.core import scheduler as sch
from repro.core import slo as slo_mod
from repro.core.blockcache import BlockCache, CacheOptions
from repro.core.prefetch import TaskPrefetcher
from repro.platform import compute as pc
from repro.platform import telemetry as tel
from repro.platform.backend import BackendOutcome, SimulatedBackend
from repro.platform.monitor import resolve_monitor_options
from repro.platform.reduce import StreamingReduceTree, finalize_stats


@dataclasses.dataclass(frozen=True)
class PlatformConfig:
    name: str
    task_sizing: str           # "kneepoint" | "large" | "tiny"
    startup_time: float        # one-time job startup (seconds)
    launch_overhead: float     # per-task launch cost (seconds)
    monitoring: bool           # task-level monitoring tax
    recovery: str              # "job" | "task"
    dfs_tax: float = 0.0       # per-task distributed-FS overhead factor


# Calibrated against Fig 5/6 (normalized to BashReduce startup ≈ 1 unit,
# ≈ 13 s on the thesis cluster; vanilla Hadoop ≈ 4×, monitoring +21%).
BASH_STARTUP = 0.050           # scaled-down unit startup for this container
PLATFORMS: Dict[str, PlatformConfig] = {
    "BTS": PlatformConfig("BTS", "kneepoint", BASH_STARTUP, 0.0005,
                          monitoring=False, recovery="job"),
    "BLT": PlatformConfig("BLT", "large", BASH_STARTUP, 0.0005,
                          monitoring=False, recovery="job"),
    "BTT": PlatformConfig("BTT", "tiny", BASH_STARTUP, 0.0005,
                          monitoring=False, recovery="job"),
    "VH": PlatformConfig("VH", "large", 4.0 * BASH_STARTUP, 0.008,
                         monitoring=True, recovery="task", dfs_tax=0.25),
    "JLH": PlatformConfig("JLH", "large", 2.0 * BASH_STARTUP, 0.004,
                          monitoring=False, recovery="job", dfs_tax=0.25),
    "LH": PlatformConfig("LH", "large", 2.0 * BASH_STARTUP, 0.004,
                         monitoring=False, recovery="job", dfs_tax=0.0),
}


# ---------------------------------------------------------------------------
# grouped platform options (the stable public configuration surface)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class WaveOptions:
    """Wave execution: batch same-shape ready tasks into one device
    dispatch (threaded backend, pallas/jnp engines)."""

    wave: str = "auto"                     # "auto" | "on" | "off"
    max_wave: int = 32                     # wave size cap (task count)
    # sharded wave execution (DESIGN.md §11) over a 1-D mesh of this
    # many devices; None keeps the plain single-device arena
    mesh_devices: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class ScheduleOptions:
    """Dynamic scheduling policy: balanced (response-time + cache
    locality) claim ranking, straggler speculation, data-plane
    prefetch, and SLO-aware pool sizing."""

    balanced: str = "auto"                 # "auto" | "on" | "off"
    speculation: str = "off"               # "off" | "on" | "auto"
    straggler_factor: float = 2.0
    prefetch: str = "auto"                 # "auto" | "on" | "off"
    slo_seconds: Optional[float] = None


@dataclasses.dataclass(frozen=True)
class ApproxOptions:
    """Error-bounded approximate queries (DESIGN.md §10): stop once the
    CI half-width at ``confidence`` falls under ``epsilon``."""

    epsilon: Optional[float] = None
    confidence: float = 0.95
    min_tasks: int = 8


@dataclasses.dataclass(frozen=True)
class FaultOptions:
    """Failure model (DESIGN.md §12): lease-based task reclamation,
    checkpoint/resume of reduce partials, bounded worker respawns."""

    lease_seconds: Optional[float] = None
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 8
    max_respawns: int = 2


# (group field name, option class, member names shared with the legacy
# flat PlatformSpec fields) — the resolution shim in __post_init__
_SPEC_GROUPS: Tuple[Tuple[str, type, Tuple[str, ...]], ...] = (
    ("waves", WaveOptions, ("wave", "max_wave", "mesh_devices")),
    ("schedule", ScheduleOptions,
     ("balanced", "speculation", "straggler_factor", "prefetch",
      "slo_seconds")),
    ("approx", ApproxOptions, ("epsilon", "confidence", "min_tasks")),
    ("faults", FaultOptions,
     ("lease_seconds", "checkpoint_dir", "checkpoint_every",
      "max_respawns")),
)


@dataclasses.dataclass(frozen=True)
class PlatformSpec:
    """Everything that selects a job's execution, in one value.

    Configuration is grouped into typed option values —
    ``PlatformSpec(schedule=ScheduleOptions(balanced="on"),
    cache=CacheOptions(capacity_bytes=1 << 20))`` — while the legacy
    flat kwargs (``PlatformSpec(balanced="on")``) keep working through
    a resolution shim that emits a :class:`DeprecationWarning`.  After
    construction both views are coherent: each flat field mirrors its
    group (``spec.balanced == spec.schedule.balanced``), and
    ``dataclasses.replace(spec, schedule=...)`` updates both.  When a
    group AND a conflicting non-default flat kwarg are passed, the
    group wins (with a warning) — flat kwargs are the migration path,
    not an override."""

    platform: str = "BTS"                  # PLATFORMS key
    n_workers: int = 2
    backend: str = "threaded"              # "threaded" | "simulated"
    engine: str = "auto"                   # compute.resolve_engine
    wave: str = "auto"                     # "auto" | "on" | "off": batch
    #   same-shape ready tasks into one device dispatch (threaded backend,
    #   pallas/jnp engines; per-task fallback for numpy & custom map_fn)
    max_wave: int = 32                     # wave size cap (task count)
    # sharded wave execution (DESIGN.md §11): partition the block arena
    # and every wave over a 1-D "wave" mesh of this many devices (must
    # not exceed jax.device_count(); CPU runs emulate 8 via XLA_FLAGS=
    # --xla_force_host_platform_device_count=8).  None keeps the plain
    # single-device arena; mesh_devices=1 routes through the sharded
    # path on a 1-device mesh.  Results are bit-identical at any mesh
    # size, and the scheduler's claim cap stays mesh-invariant so the
    # epsilon early-stop executes the same task set on every mesh.
    mesh_devices: Optional[int] = None
    # balanced dynamic scheduling (DESIGN.md §9): rank ready tasks by the
    # predicted fetch latency of their best available data-node replica
    # ("auto" engages whenever a datastore is attached; "on" requires one)
    balanced: str = "auto"                 # "auto" | "on" | "off"
    # straggler speculation: clone in-flight tasks older than
    # straggler_factor × the exec EMA onto an idle worker ("auto" gates
    # each clone through recovery.should_speculate's §3.3 cost model)
    speculation: str = "off"               # "off" | "on" | "auto"
    straggler_factor: float = 2.0
    # dynamic-k data-plane prefetch: upcoming tasks' fetches go in flight
    # while the current wave executes ("auto" engages with a datastore)
    prefetch: str = "auto"                 # "auto" | "on" | "off"
    # SLO-aware pool sizing: when set, worker count is chosen by
    # slo.choose_workers over a pow2 ladder up to n_workers (needs a
    # measured kneepoint for the throughput model; silently keeps
    # n_workers otherwise)
    slo_seconds: Optional[float] = None
    # error-bounded approximate queries (DESIGN.md §10): with an epsilon
    # target the job streams a running estimate + CI and DRAINs (cancels
    # its unexecuted tasks) once the CI half-width at `confidence` falls
    # under epsilon, after at least `min_tasks` tasks.  epsilon=None
    # keeps every path bit-identical to a full run.
    epsilon: Optional[float] = None
    confidence: float = 0.95
    min_tasks: int = 8
    # failure model (DESIGN.md §12).  ``lease_seconds`` arms lease-based
    # task reclamation: a claimed task whose lease lapses is requeued
    # (first completion wins; per-task seeds keep the race bit-exact).
    # ``checkpoint_dir`` persists completed reduce-tree partials every
    # ``checkpoint_every`` leaves so an interrupted job resumes via
    # ``Platform.run(resume_from=...)`` executing only missing tasks.
    # ``max_respawns`` bounds per-worker crash respawns.
    lease_seconds: Optional[float] = None
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 8
    max_respawns: int = 2
    knee_bytes: Optional[float] = None     # skip the offline phase if set
    kneepoint_sizes: Tuple[int, ...] = (1, 2, 4, 8, 16, 32, 64)
    seed: int = 0
    task_sizing: Optional[str] = None      # override the config's sizing
    startup_time: Optional[float] = None   # override the config's startup
    startup_scale: float = 1.0             # sim: thesis-scale startup
    compute_values: bool = True            # sim: real partials vs cost-only
    sim_workers: Optional[Tuple[sch.SimWorker, ...]] = None
    scheduler: Optional[sch.SchedulerConfig] = None
    # unified telemetry (DESIGN.md §13): None/False ⇒ disabled no-op
    # sink (results bit-identical either way), True/"on" ⇒ record into
    # bounded rings, or an explicit telemetry.TelemetryConfig
    telemetry: Any = None
    # grouped option values (the stable configuration surface).  None ⇒
    # synthesized from the legacy flat fields above by __post_init__;
    # when provided, the group is authoritative and the flat mirrors
    # are synced to it.
    waves: Optional[WaveOptions] = None
    schedule: Optional[ScheduleOptions] = None
    approx: Optional[ApproxOptions] = None
    faults: Optional[FaultOptions] = None
    # worker-side block cache (DESIGN.md §14); the default
    # CacheOptions() has capacity_bytes=0 ⇒ disabled, bit-identical to
    # the uncached platform
    cache: Optional[CacheOptions] = None
    # SLO monitor / critical-path / diagnosis layer (DESIGN.md §15);
    # None/False ⇒ disabled (no tap, zero new events, bit-identical),
    # True/"on" ⇒ enabled defaults, or an explicit MonitorOptions
    monitor: Any = None

    def __post_init__(self) -> None:
        for gname, gcls, members in _SPEC_GROUPS:
            defaults = {f.name: f.default for f in dataclasses.fields(gcls)}
            group = getattr(self, gname)
            if group is None:
                # legacy flat view: synthesize the group from the flat
                # fields; warn only when a flat kwarg was actually used
                flat = {m: getattr(self, m) for m in members}
                changed = [m for m in members if flat[m] != defaults[m]]
                if changed:
                    warnings.warn(
                        f"flat PlatformSpec field(s) {changed} are "
                        f"deprecated; pass {gname}="
                        f"{gcls.__name__}(...) instead",
                        DeprecationWarning, stacklevel=3)
                object.__setattr__(self, gname, gcls(**flat))
            else:
                # grouped view: the group wins; a conflicting
                # non-default flat kwarg is superseded (with a warning)
                clash = [m for m in members
                         if getattr(self, m) != defaults[m]
                         and getattr(self, m) != getattr(group, m)]
                if clash:
                    warnings.warn(
                        f"flat PlatformSpec field(s) {clash} are "
                        f"superseded by the {gname}= option group",
                        DeprecationWarning, stacklevel=3)
                for m in members:
                    object.__setattr__(self, m, getattr(group, m))
        if self.cache is None:
            object.__setattr__(self, "cache", CacheOptions())
        object.__setattr__(self, "monitor",
                           resolve_monitor_options(self.monitor))


def make_tasks(sample_sizes: Sequence[int], sizing: str,
               knee_bytes: Optional[float], n_workers: int) -> List[sch.Task]:
    """Partition samples into tasks per the config's sizing policy."""
    total = float(sum(sample_sizes))
    if sizing == "tiny":
        groups = [[i] for i in range(len(sample_sizes))]
    elif sizing == "large":
        # all samples partitioned to a node in one file (Sn samples/task)
        per_node = total / max(n_workers, 1)
        groups = kp.pack_tasks_by_count(sample_sizes, per_node)
    else:
        assert knee_bytes is not None, "kneepoint sizing needs a knee"
        groups = kp.pack_tasks_by_count(sample_sizes, knee_bytes)
    out = []
    for tid, g in enumerate(groups):
        out.append(sch.Task(
            task_id=tid, sample_ids=tuple(g),
            size_bytes=float(sum(sample_sizes[i] for i in g))))
    return out


MapFn = Callable[[sch.Task, np.ndarray, np.ndarray, int], Dict[str, Any]]


class CustomMap:
    """A workload computed by a caller's per-task callable
    ``map_fn(task, block, months, seed) -> partial`` in place of a
    statistic engine (overhead benchmarks): engine ``"custom"``, per-task
    dispatch, partials reduced as they are.  Identity is the instance, so
    two callables never share a query class."""

    statistic = "custom"
    name = "custom"

    def __init__(self, map_fn: MapFn):
        self.map_fn = map_fn


def resolve_job_engine(workload, engine: str) -> str:
    """``"custom"`` for a :class:`CustomMap`, else the statistic's engine
    (:func:`~repro.platform.compute.resolve_engine`)."""
    if isinstance(workload, CustomMap):
        return "custom"
    return pc.resolve_engine(workload.statistic, engine)


def measure_kneepoint(samples: Dict[int, np.ndarray],
                      months: Dict[int, np.ndarray],
                      workload,
                      sizes: Sequence[int] = (1, 2, 4, 8, 16, 32, 64),
                      *,
                      engine: str = "auto",
                      ) -> Tuple[kp.KneepointResult, float]:
    """Offline phase (Fig 3): run isolated map tasks of increasing block
    size, record per-sample wall time (the cost-per-byte miss proxy of
    DESIGN.md §2), find the knee.  A :class:`CustomMap` curve is measured
    on the custom compute that will actually execute."""
    ids = sorted(samples)
    sample_bytes = np.mean([samples[i].nbytes for i in ids])
    eng = resolve_job_engine(workload, engine)

    def exec_task(n: int) -> float:
        n = min(n, len(ids))
        block = np.stack(pc.pad_to_common([samples[i] for i in ids[:n]]))
        mo = np.stack(pc.pad_to_common([months[i] for i in ids[:n]]))
        t0 = time.perf_counter()
        if eng == "custom":
            probe = sch.Task(task_id=-1, sample_ids=tuple(range(n)),
                             size_bytes=float(n * sample_bytes))
            workload.map_fn(probe, block, mo, 0)
        else:
            pc.run_map_task(block, mo, 0, workload, eng)
        return (time.perf_counter() - t0) / n

    curve = kp.measure_curve(exec_task, [s for s in sizes
                                         if s <= len(ids)], repeats=3)
    curve = [kp.CurvePoint(p.task_size * sample_bytes, p.cost)
             for p in curve]
    res = kp.find_kneepoint(curve)
    return res, res.task_size


def measure_per_sample_cost(samples: Dict[int, np.ndarray],
                            months: Dict[int, np.ndarray],
                            workload, *, block: int = 8,
                            engine: str = "auto", repeats: int = 3) -> float:
    """Median seconds per sample for a ``block``-sized map task — the
    calibration input for
    :meth:`~repro.platform.facade.Platform.run_scaleout` cost models."""
    ids = sorted(samples)[:block]
    arr = np.stack(pc.pad_to_common([samples[i] for i in ids]))
    mo = np.stack(pc.pad_to_common([months[i] for i in ids]))
    eng = pc.resolve_engine(workload.statistic, engine)
    pc.run_map_task(arr, mo, 0, workload, eng)           # warm/compile
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        pc.run_map_task(arr, mo, 0, workload, eng)
        times.append(time.perf_counter() - t0)
    return sorted(times)[len(times) // 2] / len(ids)


# ---------------------------------------------------------------------------
# Reusable job phases (plan → wave/task contexts) — the substrate of the
# service's query classes and of the virtual-time run
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class JobPlan:
    """Output of the plan phase: kneepoint + task partition + block-shape
    policy.  Everything execution needs, decoupled from the driver so the
    service can compute it once per (dataset, query class) and serve many
    jobs from it."""

    engine: str
    tasks: List[sch.Task]
    ids: List[int]                      # sorted sample keys
    total_bytes: float
    knee_bytes: Optional[float]
    knee_res: Optional[kp.KneepointResult]
    pad_len: int
    max_count: int
    task_shape: Callable[[sch.Task], Tuple[int, int]]
    build_block: Callable[[sch.Task], Tuple[np.ndarray, np.ndarray]]
    plan_seconds: float = 0.0           # offline kneepoint time
    partition_seconds: float = 0.0      # task partition time


def plan_job(samples: Dict[int, np.ndarray],
             months: Dict[int, np.ndarray],
             workload, *,
             sizing: str,
             engine: str,
             n_exec: int,
             knee_bytes: Optional[float] = None,
             kneepoint_sizes: Sequence[int] = (1, 2, 4, 8, 16, 32, 64),
             ) -> JobPlan:
    """Phases 1-2 of the data path minus datastore placement: measure the
    kneepoint if the sizing policy needs one, partition samples into
    tasks, and derive the padded-shape / block-building closures.  A
    :class:`CustomMap` is calibrated on its own callable."""
    ids = sorted(samples)
    sizes = [samples[i].nbytes for i in ids]
    knee_res = None
    t0 = time.perf_counter()
    if sizing == "kneepoint" and knee_bytes is None:
        knee_res, knee_bytes = measure_kneepoint(
            samples, months, workload, sizes=kneepoint_sizes, engine=engine)
    plan_seconds = time.perf_counter() - t0
    t0 = time.perf_counter()
    tasks = make_tasks(sizes, sizing, knee_bytes, n_exec)
    max_count = max(len(t.sample_ids) for t in tasks)
    pad_len = (0 if engine == "custom" else
               pc.partial_pad_len(workload.statistic, samples))

    shapes: Dict[Tuple[int, ...], Tuple[int, int]] = {}

    def task_shape(task: sch.Task) -> Tuple[int, int]:
        """Padded block shape, derived from row lengths without
        materializing the block (same policy as pad_to_common), once per
        task: every claim's fuse-key scan asks for it under the pool's
        lock."""
        shape = shapes.get(task.sample_ids)
        if shape is None:
            longest = max(samples[ids[i]].shape[0] for i in task.sample_ids)
            shape = shapes[task.sample_ids] = (
                max_count, pc.padded_len(longest, pad_len))
        return shape

    def build_block(task: sch.Task) -> Tuple[np.ndarray, np.ndarray]:
        return pc.build_block(samples, months, ids, task.sample_ids,
                              max_count, pad_len)

    return JobPlan(
        engine=engine, tasks=tasks, ids=ids,
        total_bytes=float(sum(sizes)), knee_bytes=knee_bytes,
        knee_res=knee_res, pad_len=pad_len, max_count=max_count,
        task_shape=task_shape, build_block=build_block,
        plan_seconds=plan_seconds,
        partition_seconds=time.perf_counter() - t0)


class WaveContext:
    """Device-resident execution state for one query class: the packed
    :class:`~repro.platform.compute.BlockArena`, one fixed wave width per
    shape bucket, and the warmed kernels.  Built once (upload + compile
    are startup cost), then every wave — from this job or, in the
    service, from ANY job on the same dataset/workload/engine — ships
    only its slot and seed vectors."""

    def __init__(self, arena: pc.BlockArena, wave_pad: Dict[Any, int],
                 workload, engine: str,
                 task_shape: Callable[[sch.Task], Any]):
        self.arena = arena
        self.wave_pad = wave_pad
        self.workload = workload
        self.engine = engine
        self.task_shape = task_shape

    def cap(self, task: sch.Task) -> int:
        """The fixed padded wave width of this task's shape bucket."""
        return self.wave_pad[self.task_shape(task)]

    def run(self, tasks: List[sch.Task], seeds: np.ndarray,
            clock: Optional[tel.SpanClock] = None
            ) -> List[Dict[str, np.ndarray]]:
        return pc.run_map_wave(self.arena, tasks, seeds, self.workload,
                               self.engine, pad_to=self.cap(tasks[0]),
                               clock=clock)

    def wave_bytes(self, n: int) -> float:
        """Host→device traffic of an n-task wave: slot + seed vectors
        only (the arena is resident)."""
        return 2.0 * n * np.dtype(np.int32).itemsize


def build_wave_context(plan: JobPlan, workload, *, n_exec: int,
                       max_wave: int, warm_seed: int = 0,
                       mesh=None) -> WaveContext:
    """Pack the plan's blocks into the device arena, pin one wave width
    per shape bucket, and warm one full-size wave per bucket so exactly
    ONE kernel shape compiles per bucket (a tail wave can never recompile
    mid-job); buckets split across workers so one worker cannot swallow
    a bucket in a single wave while its peers idle.

    With ``mesh`` (a ``launch.mesh.make_wave_mesh`` 1-D mesh) the arena
    is partitioned over its devices and waves dispatch sharded.  The
    ``wave_pad`` claim caps are computed identically either way — they
    drive the *scheduler's* wave partition, which must stay
    mesh-invariant for the epsilon early-stop path to settle at the
    same task counts on every mesh size; only the per-device kernel
    width inside the sharded dispatch varies with the mesh."""
    lanes, months = pc.arena_layout(workload.statistic, plan.engine)
    if mesh is not None:
        arena: pc.BlockArena = pc.ShardedBlockArena.pack(
            plan.tasks, plan.task_shape, plan.build_block, mesh,
            lanes=lanes, months=months)
    else:
        arena = pc.BlockArena.pack(plan.tasks, plan.task_shape,
                                   plan.build_block, lanes=lanes,
                                   months=months)
    by_key: Dict[Any, List[sch.Task]] = {}
    for task in plan.tasks:
        by_key.setdefault(plan.task_shape(task), []).append(task)
    n_exec = max(n_exec, 1)
    wave_pad = {
        key: pc.pow2_ceil(min(max_wave, -(-len(group) // n_exec)))
        for key, group in by_key.items()}
    for key, group in by_key.items():
        warm = group[:min(wave_pad[key], len(group))]
        pc.run_map_wave(arena, warm,
                        np.full(len(warm), warm_seed, np.int32),
                        workload, plan.engine, pad_to=wave_pad[key])
    return WaveContext(arena, wave_pad, workload, plan.engine,
                       plan.task_shape)


def resolve_platform_config(spec: PlatformSpec) -> PlatformConfig:
    """The overhead profile a spec selects, with per-spec overrides."""
    if spec.platform not in PLATFORMS:
        raise ValueError(
            f"unknown platform config {spec.platform!r}; "
            f"choose one of {sorted(PLATFORMS)}")
    plat = PLATFORMS[spec.platform]
    overrides = {}
    if spec.task_sizing is not None:
        overrides["task_sizing"] = spec.task_sizing
    if spec.startup_time is not None:
        overrides["startup_time"] = spec.startup_time
    return dataclasses.replace(plat, **overrides) if overrides else plat


def wave_enabled(spec: PlatformSpec, engine: str, workload) -> bool:
    """Wave execution needs the threaded backend (the simulator
    calibrates per-task costs) and a device engine (not a custom
    map_fn); ``wave="on"`` makes an unsupported combination an error
    instead of a silent per-task fallback.  ``"auto"`` additionally
    requires the workload to be dispatch-overhead-bound (small per-task
    draw volume) — batching heavy tasks pays pad compute for nothing."""
    if spec.wave not in ("auto", "on", "off"):
        raise ValueError(f"unknown wave mode {spec.wave!r}; "
                         "choose 'auto', 'on' or 'off'")
    if spec.wave == "off" or spec.max_wave <= 1:
        return False
    supported = spec.backend == "threaded" and pc.wave_supported(engine)
    if spec.wave == "on" and not supported:
        raise ValueError(
            "wave='on' needs the threaded backend and a device engine "
            f"(pallas|jnp); got backend={spec.backend!r}, "
            f"engine={engine!r}")
    if spec.wave == "auto":
        return supported and pc.wave_profitable(workload)
    return supported


def resolve_wave_mesh(spec: PlatformSpec, wave_on: bool):
    """Build the 1-D wave mesh a spec asks for, or ``None``.

    Like the other mode resolvers, an impossible request is an error,
    never a silent fallback: ``mesh_devices`` without wave execution
    would shard nothing, and asking for more devices than exist fails
    in ``make_wave_mesh`` with the XLA_FLAGS hint."""
    if spec.mesh_devices is None:
        return None
    if spec.mesh_devices < 1:
        raise ValueError(
            f"mesh_devices must be >= 1, got {spec.mesh_devices}")
    if not wave_on:
        raise ValueError(
            "mesh_devices shards wave execution, which this spec "
            "disables — it needs the threaded backend, a device engine "
            "(pallas|jnp) and wave != 'off'")
    from repro.launch.mesh import make_wave_mesh

    return make_wave_mesh(spec.mesh_devices)


def balanced_enabled(spec: PlatformSpec, has_datastore: bool) -> bool:
    """Response-time-aware claim ordering needs a data plane to score;
    ``balanced="on"`` makes its absence an error instead of a silent
    FIFO fallback."""
    if spec.balanced not in ("auto", "on", "off"):
        raise ValueError(f"unknown balanced mode {spec.balanced!r}; "
                         "choose 'auto', 'on' or 'off'")
    if spec.balanced == "off":
        return False
    if spec.balanced == "on" and not has_datastore:
        raise ValueError("balanced='on' needs a datastore to score "
                         "replicas against")
    return has_datastore


def prefetch_enabled(spec: PlatformSpec, has_fetch: bool) -> bool:
    """Like :func:`balanced_enabled`: ``"on"`` makes a configuration
    that cannot prefetch an error instead of a silent inline-fetch
    fallback (no datastore to fetch from, or a virtual-time backend
    that models the overlap itself)."""
    if spec.prefetch not in ("auto", "on", "off"):
        raise ValueError(f"unknown prefetch mode {spec.prefetch!r}; "
                         "choose 'auto', 'on' or 'off'")
    if spec.prefetch == "on":
        if not has_fetch:
            raise ValueError("prefetch='on' needs a datastore whose "
                             "fetches can be pipelined")
        if spec.backend == "simulated":
            raise ValueError("prefetch='on' needs the threaded backend "
                             "(the simulator models the §3.5 overlap "
                             "in virtual time)")
    return (spec.prefetch != "off" and has_fetch
            and spec.backend == "threaded")


def resolve_speculation(spec: PlatformSpec):
    """Map the spec's speculation mode onto SchedulerConfig.speculative."""
    if spec.speculation not in ("off", "on", "auto"):
        raise ValueError(f"unknown speculation mode {spec.speculation!r}; "
                         "choose 'off', 'on' or 'auto'")
    return {"off": False, "on": True, "auto": "auto"}[spec.speculation]


def build_prefetcher(n_workers: int) -> TaskPrefetcher:
    """The platform's prefetch pipe: ~2 waves/worker of look-ahead and
    one background fetch stream per worker.  Deeper pipes cannot raise
    data-plane throughput past nodes × parallelism / latency — they
    only add queueing (the contention term of §3.5)."""
    return TaskPrefetcher(min_depth=max(2, n_workers),
                          max_depth=max(4, 2 * n_workers),
                          workers=max(2, min(2 * n_workers, 8)))


def slo_worker_decision(spec: PlatformSpec, plat: PlatformConfig,
                        plan: JobPlan) -> Optional[slo_mod.ScaleDecision]:
    """SLO-aware pool sizing (thesis §4.2.3 / Fig 12-13): with a target
    ``slo_seconds`` and a measured kneepoint, choose the worker count
    that maximizes data within the SLO window — small jobs under tight
    SLOs get *fewer* workers because startup dominates.  ``None`` when
    no SLO is set or the knee was not measured (no throughput model)."""
    if spec.slo_seconds is None or plan.knee_res is None:
        return None
    cost = plan.knee_res.curve[plan.knee_res.index].cost  # s per sample
    if cost <= 0 or not plan.ids:
        return None
    sample_bytes = plan.total_bytes / len(plan.ids)
    return slo_mod.choose_workers(
        max(spec.n_workers, 1),
        bytes_per_second_per_worker=sample_bytes / cost,
        startup_seconds=plat.startup_time,
        slo_seconds=spec.slo_seconds)


class JobCheckpointer:
    """Persist completed reduce-tree leaf partials during execution
    (DESIGN.md §12).  Every ``every`` newly completed leaves the full
    set of accumulated partials is saved through
    :class:`~repro.checkpoint.manager.CheckpointManager` (atomic
    tmp+rename, async, fsynced), so a crash at ANY point leaves the
    newest committed step restorable.  :meth:`load` gives the partials
    back as ``{task_id: {name: array}}``; the resumed job offers them
    into a full-size reduce tree and executes only the missing tasks —
    the tree's fixed shape makes the combined result bit-identical to
    an uninterrupted run.

    ``injector`` is an optional
    :class:`~repro.platform.faults.FaultInjector` whose
    :meth:`~repro.platform.faults.FaultInjector.checkpoint_tick` fires
    planned mid-save crashes."""

    def __init__(self, directory: str, n_tasks: int, *, every: int = 8,
                 restored: Optional[Dict[int, Dict[str, Any]]] = None,
                 injector=None, keep: int = 2, telemetry=None):
        self.mgr = CheckpointManager(directory, keep=keep)
        self.n_tasks = n_tasks
        self.every = max(int(every), 1)
        self.injector = injector
        self.telemetry = telemetry
        self.saves = 0
        self._lock = threading.Lock()
        self._partials: Dict[int, Dict[str, Any]] = dict(restored or {})
        self._since = 0
        self._step = self.mgr.all_steps()[-1] if self.mgr.all_steps() \
            else 0

    def offer(self, task_id: int, value: Any) -> None:
        """Record one completed leaf; saves when a full interval of new
        leaves has accumulated.  The save snapshot is taken under the
        lock; serialization runs on the manager's background thread."""
        due = False
        with self._lock:
            if task_id not in self._partials:
                self._partials[task_id] = value
                self._since += 1
                if self._since >= self.every:
                    self._since = 0
                    self._step += 1
                    step = self._step
                    snap = dict(self._partials)
                    due = True
        if not due:
            return
        if self.injector is not None:
            # a planned crash hits this save where a real one would: the
            # manager writes one save at a time, so the previous step
            # has committed by now
            self.mgr.wait()
            self.injector.checkpoint_tick()
        state: Dict[str, np.ndarray] = {}
        for tid, partial in snap.items():
            for name, arr in partial.items():
                state[f"{tid}/{name}"] = np.asarray(arr)
        state["__meta__/completed"] = np.asarray(sorted(snap),
                                                 dtype=np.int64)
        state["__meta__/n_tasks"] = np.asarray(self.n_tasks,
                                               dtype=np.int64)
        self.mgr.save(step, state)
        self.saves += 1
        if self.telemetry is not None:
            self.telemetry.emit("checkpoint_saved", step=step,
                                n_leaves=len(snap))

    def finish(self) -> None:
        """Join the in-flight save and surface any parked background
        error (satellite of the §12 durability contract: a failed async
        save must fail the job, never vanish)."""
        self.mgr.wait()

    @staticmethod
    def load(directory: str, n_tasks: int) -> Dict[int, Dict[str, Any]]:
        """Restore ``{task_id: partial}`` from the newest committed
        checkpoint of a job of ``n_tasks`` tasks; ``{}`` when none
        exists.  A checkpoint of another plan is an error: resume needs
        the same dataset, workload, sizing and knee."""
        mgr = CheckpointManager(directory)
        flat = mgr.restore_latest()
        if flat is None:
            return {}
        partials: Dict[int, Dict[str, Any]] = {}
        for key, arr in flat.items():
            # names are jax keystr forms of flat-dict keys: "['12/sum']"
            if key.startswith("['") and key.endswith("']"):
                key = key[2:-2]
            if key.startswith("__meta__/"):
                if key == "__meta__/n_tasks" and int(arr) != n_tasks:
                    raise ValueError(
                        f"checkpoint at {directory!r} holds partials for "
                        f"{int(arr)} tasks but this plan has {n_tasks} — "
                        "resume needs the same dataset, workload, sizing "
                        "and knee")
                continue
            tid, name = key.split("/", 1)
            partials.setdefault(int(tid), {})[name] = np.asarray(arr)
        return partials


def scheduler_config(spec: PlatformSpec,
                     plat: PlatformConfig) -> sch.SchedulerConfig:
    """The virtual-time two-phase policy a spec selects."""
    if spec.scheduler is not None:
        return spec.scheduler
    return sch.SchedulerConfig(
        recovery=plat.recovery, seed=spec.seed,
        speculative=resolve_speculation(spec),
        straggler_factor=spec.straggler_factor)


def run_virtual(spec: PlatformSpec, samples: Dict[int, np.ndarray],
                months: Dict[int, np.ndarray], workload, *,
                datastore=None, fault_injector=None,
                resume_from: Optional[str] = None,
                telemetry: Optional[tel.TelemetryBus] = None
                ) -> Dict[str, Any]:
    """One job in virtual time: kneepoint → distribute →
    :class:`~repro.platform.backend.SimulatedBackend` (real compute
    measured once per task, then the two-phase policy replayed at the
    spec's worker count) → streaming reduce.  Returns the
    :class:`~repro.platform.facade.JobReport` fields.

    ``resume_from`` names a checkpoint directory written by a prior
    (interrupted) run of the same job: its committed leaf partials are
    restored into the reduce tree and only the missing tasks execute —
    bit-identical to an uninterrupted run (§12).  ``telemetry`` is a
    virtual bus for the run's events (a disabled one by default)."""
    bus = (telemetry if telemetry is not None
           else tel.TelemetryBus(virtual=True))
    plat = resolve_platform_config(spec)
    engine = resolve_job_engine(workload, spec.engine)
    phases: Dict[str, float] = {}
    # validated up front: balanced="on" without a datastore (and any
    # bad mode string) must error, never silently run FIFO; the same
    # for waves, a mesh and prefetch, which virtual time models itself
    balanced_on = balanced_enabled(spec, datastore is not None)
    resolve_wave_mesh(spec, wave_enabled(spec, engine, workload))
    prefetch_enabled(spec, datastore is not None)
    n_exec = len(spec.sim_workers) if spec.sim_workers else spec.n_workers

    # phases 1-2 — offline kneepoint (thesis §3.2: ≈3% of online time),
    # then partition + distribute onto the data plane
    plan = plan_job(samples, months, workload, sizing=plat.task_sizing,
                    engine=engine, n_exec=n_exec,
                    knee_bytes=spec.knee_bytes,
                    kneepoint_sizes=spec.kneepoint_sizes)
    phases["plan"] = plan.plan_seconds
    bus.emit("job_planned", n_tasks=len(plan.tasks),
             knee_bytes=plan.knee_bytes, engine=engine)
    if datastore is not None:
        datastore.telemetry = bus
        # worker-side block cache (DESIGN.md §14): attached once and
        # kept on the store across runs
        if spec.cache.enabled and datastore.cache is None:
            datastore.cache = BlockCache(spec.cache)
    if fault_injector is not None:
        fault_injector.telemetry = bus
    t0 = time.perf_counter()
    if datastore is not None:
        datastore.put_all({i: samples[i] for i in plan.ids})
        if balanced_on:
            # seed the per-node response-time EMAs (phase-1 probe of
            # the data plane) so the first claims are not blind
            datastore.probe()
    phases["distribute"] = (plan.partition_seconds
                            + time.perf_counter() - t0)
    tasks, ids = plan.tasks, plan.ids

    # resume (DESIGN.md §12): restore committed leaf partials and
    # execute only the missing tasks; the tree keeps its full shape so
    # the combined result is bit-identical to an unbroken run
    restored = (JobCheckpointer.load(resume_from, len(tasks))
                if resume_from is not None else {})
    run_tasks = ([t for t in tasks if t.task_id not in restored]
                 if restored else tasks)

    # SLO-aware pool sizing (slo.choose_workers over the knee-derived
    # throughput model); explicit sim worker lists are respected
    decision = (None if spec.sim_workers
                else slo_worker_decision(spec, plat, plan))
    n_eff = decision.cores if decision is not None else n_exec
    # all dispatch accounting flows through the bus's aggregation path
    # into this one sink (DESIGN.md §13)
    dispatch = pc.DispatchStats()
    bus.bind_dispatch(dispatch)

    # phase 3 — compile warmup: one kernel per distinct block shape, so
    # the calibration pass measures execution, not compilation; the
    # warm blocks are reused by phase 4
    t0 = time.perf_counter()
    block_cache: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
    if engine in ("jnp", "pallas"):
        seen = set()
        for task in tasks:
            key = plan.task_shape(task)
            if key not in seen:
                seen.add(key)
                block, mo = block_cache[task.task_id] = \
                    plan.build_block(task)
                pc.run_map_task(block, mo, spec.seed + task.task_id,
                                workload, engine)
    phases["compile"] = time.perf_counter() - t0

    def compute_task(task: sch.Task):
        cached = block_cache.pop(task.task_id, None)
        block, mo = cached if cached is not None else \
            plan.build_block(task)
        task_seed = spec.seed + task.task_id
        if engine == "custom":
            return workload.map_fn(task, block, mo, task_seed)
        if engine in ("jnp", "pallas"):
            bus.emit("task_dispatched", task_id=task.task_id,
                     nbytes=float(block.nbytes) + (
                         float(mo.nbytes) if engine == "jnp" else 0.0))
        return pc.run_map_task(block, mo, task_seed, workload, engine)

    fetch = None
    locality_score = None
    if datastore is not None:
        def fetch(task: sch.Task):
            datastore.fetch_many([ids[sid] for sid in task.sample_ids])

        if balanced_on:
            def locality_score(task: sch.Task) -> float:
                return datastore.predicted_task_fetch(
                    [ids[sid] for sid in task.sample_ids])

    # phase 4 — execute; partials stream into the reduce tree.  With an
    # epsilon target (DESIGN.md §10) the calibration computes EVERY
    # partial (that is how the simulator measures costs); they are
    # captured so the replay stopper observes only virtually-completed
    # tasks and the final reduce covers only those
    statistic = workload.statistic
    approx = spec.epsilon is not None
    # validated before the tree exists: a constructor ValueError below
    # would leak the tree's combiner thread
    est_mod.validate_error_target(spec.epsilon, spec.confidence)
    if approx and not spec.compute_values:
        raise ValueError(
            "epsilon needs computed partials to estimate from; "
            "simulated specs must keep compute_values=True")
    tree, stopper, sim_partials = None, None, None
    emit: Callable[[int, Any], None] = lambda tid, v: None
    if spec.compute_values:
        tree = StreamingReduceTree(len(tasks))
        emit = tree.offer
        if approx:
            sim_partials = {}

            def emit(tid, v, _offer=tree.offer, _cap=sim_partials):
                _cap[tid] = v
                _offer(tid, v)

            stopper = est_mod.ReplayStopper(
                est_mod.SubsampleEstimator(statistic, spec.confidence),
                spec.epsilon, partials=sim_partials,
                min_tasks=spec.min_tasks)
    # restored leaves enter the tree (and any estimator) first, exactly
    # as if those tasks had just completed — BEFORE the checkpoint/
    # injector wraps so they neither re-save nor tick the injector's
    # completion clock
    for tid in sorted(restored):
        emit(tid, restored[tid])
    if restored:
        bus.emit("checkpoint_restored", n=len(restored),
                 task_ids=tuple(sorted(restored)))
    ckpt: Optional[JobCheckpointer] = None
    if spec.checkpoint_dir is not None and tree is not None:
        ckpt = JobCheckpointer(
            spec.checkpoint_dir, len(tasks), every=spec.checkpoint_every,
            restored=restored, injector=fault_injector, telemetry=bus)
        prev_emit = emit

        def emit(tid, v, _prev=prev_emit, _c=ckpt):
            _prev(tid, v)
            _c.offer(tid, v)

    if fault_injector is not None:
        if datastore is not None:
            fault_injector.attach_store(datastore)
        emit = fault_injector.wrap_emit(emit)
    # execute-window anchor for the critical-path analyzer: 0.0 on the
    # virtual bus — the sim clock opens at startup_time, so the window
    # equals the virtual makespan
    t_execute = bus.now()
    backend = SimulatedBackend(
        list(spec.sim_workers) if spec.sim_workers else n_eff,
        compute_values=spec.compute_values,
        startup_scale=spec.startup_scale)
    t0 = time.perf_counter()
    try:
        outcome = backend.run(
            run_tasks, compute=compute_task, fetch=fetch, plat=plat,
            cfg=scheduler_config(spec, plat), emit=emit,
            shape_key=plan.task_shape, locality_score=locality_score,
            stopper=stopper, telemetry=bus)
        phases["execute"] = time.perf_counter() - t0
        if ckpt is not None:
            # surface any parked async-save error: a job that "ran" but
            # silently failed to persist its restore point must not
            # report success (§12 durability contract)
            ckpt.finish()

        # phase 5 — drain the reduce tree, finalize the statistic.  An
        # early-stopped job finalizes over its executed subset in the
        # same fixed tree order (deterministic for the set).
        t0 = time.perf_counter()
        result, reduce_info = None, None
        if tree is not None:
            if stopper is not None and stopper.stopped:
                executed = ({r.task_id for r in outcome.results}
                            | set(restored))
                root = StreamingReduceTree.combine_subset(
                    len(tasks), {tid: sim_partials[tid]
                                 for tid in sorted(executed)})
                tree.close()           # full-leaf stream, unused now
            else:
                root = tree.result(timeout=600.0)
            result = finalize_stats(root, statistic)
            reduce_info = tree.stats()
        phases["reduce"] = time.perf_counter() - t0
    except BaseException:
        if tree is not None:
            tree.close()               # unblock the combiner thread
        raise
    finally:
        if datastore is not None:
            datastore.telemetry = None

    if datastore is not None:
        for r in outcome.results:
            datastore.report_exec_time(r.exec_time)
    if stopper is not None:
        ci = stopper.snapshot()
        if ci is not None:
            bus.emit("ci_snapshot", **ci.as_dict())
    bus.emit("job_done", makespan=outcome.makespan,
             tasks_executed=len({r.task_id for r in outcome.results}),
             t_execute=t_execute,
             startup_seconds=plat.startup_time * spec.startup_scale,
             reduce_seconds=phases["reduce"])
    return report_fields(
        spec, plat, outcome, tasks, plan.total_bytes, plan.knee_bytes,
        plan.knee_res, engine, phases, result, reduce_info,
        datastore=datastore, dispatch=dispatch, scale_decision=decision,
        n_workers_used=n_eff, stopper=stopper,
        tasks_restored=len(restored),
        checkpoint_saves=ckpt.saves if ckpt is not None else 0,
        fault_events=(len(fault_injector.fired)
                      if fault_injector is not None else 0))


def report_fields(spec: PlatformSpec, plat: PlatformConfig,
                  outcome: BackendOutcome, tasks: List[sch.Task],
                  total_bytes: float, knee_bytes: Optional[float],
                  knee_res: Optional[kp.KneepointResult], engine: str,
                  phases: Dict[str, float], result, reduce_info, *,
                  datastore=None,
                  dispatch: Optional[pc.DispatchStats] = None,
                  scale_decision: Optional[slo_mod.ScaleDecision] = None,
                  n_workers_used: int = 0, stopper=None,
                  tasks_restored: int = 0, checkpoint_saves: int = 0,
                  fault_events: int = 0) -> Dict[str, Any]:
    """The :class:`~repro.platform.facade.JobReport` fields of one
    virtual-time run."""
    dispatch = dispatch or pc.DispatchStats()
    execs = sorted(r.exec_time for r in outcome.results)
    median = execs[len(execs) // 2] if execs else 0.0
    executed = len({r.task_id for r in outcome.results})
    snap = stopper.snapshot() if stopper is not None else None
    cache = datastore.cache if datastore is not None else None
    return dict(
        platform=plat.name,
        n_tasks=len(tasks),
        task_size_bytes=(knee_bytes if knee_bytes is not None
                         else total_bytes / max(len(tasks), 1)),
        makespan=outcome.makespan,
        throughput_bps=total_bytes / max(outcome.makespan, 1e-12),
        startup_time=plat.startup_time * spec.startup_scale,
        result=result,
        kneepoint=knee_res,
        backend="simulated",
        engine=engine,
        phases=phases,
        queue_depths=outcome.queue_depths,
        miss_curve=knee_res.curve if knee_res is not None else (),
        max_task_bytes=max((t.size_bytes for t in tasks), default=0.0),
        stragglers=sum(1 for e in execs if median and e > 2.0 * median),
        speculative_launches=outcome.speculative_launches,
        restarts=outcome.restarts,
        calibration_seconds=outcome.calibration_seconds,
        datastore_stats=(datastore.stats()
                         if datastore is not None else None),
        reduce_info=reduce_info,
        device_dispatches=dispatch.device_dispatches,
        bytes_uploaded=dispatch.bytes_uploaded,
        wave_sizes=list(dispatch.wave_sizes),
        speculation_wins=outcome.speculation_wins,
        scale_decision=(f"{scale_decision.cores} cores: "
                        f"{scale_decision.reason}"
                        if scale_decision is not None else None),
        n_workers_used=n_workers_used,
        cache_stats=cache.stats() if cache is not None else None,
        tasks_executed=executed + tasks_restored,
        tasks_cancelled=max(len(tasks) - executed - tasks_restored, 0),
        stop_reason=stopper.stop_reason if stopper is not None else None,
        final_ci=snap.as_dict() if snap is not None else None,
        tasks_restored=tasks_restored,
        checkpoint_saves=checkpoint_saves,
        fault_events=fault_events)
