"""``repro.platform`` — the end-to-end tiny-task platform driver.

Composes the thesis' pieces (kneepoint task sizing → replicated datastore →
dynamic scheduler → streaming reduce) into one pipeline with one
real-time execution path, the multi-tenant :class:`PlatformService`, and
a virtual-time model beside it (scale-out studies).  :class:`Platform`
runs one job on either.  See DESIGN.md §1-§2 and the thesis §3
(arXiv:1404.4653).

This module is the stable import surface: ``__all__`` below is the
curated public API — the driver (:class:`Platform`, :class:`PlatformSpec`
and its grouped option values), the multi-tenant service
(:class:`PlatformService`, :class:`JobTicket`, :class:`JobReport`), and
telemetry configuration (:class:`TelemetryConfig`).  Everything else
re-exported here is platform plumbing that may move between submodules;
import it from its home module if you need it.
"""

from repro.core.blockcache import BlockCache, CacheOptions  # noqa: F401
from repro.platform.backend import (  # noqa: F401
    BackendOutcome,
    PoolJob,
    ServicePool,
    SimulatedBackend,
)
from repro.platform.compute import (  # noqa: F401
    MOMENTS,
    BlockArena,
    DispatchStats,
    MomentsSpec,
    build_block,
    pad_to_common,
    resolve_engine,
    run_map_task,
    run_map_wave,
    wave_supported,
)
from repro.platform.driver import (  # noqa: F401
    BASH_STARTUP,
    PLATFORMS,
    ApproxOptions,
    FaultOptions,
    JobPlan,
    PlatformConfig,
    PlatformSpec,
    ScheduleOptions,
    WaveContext,
    WaveOptions,
    build_wave_context,
    make_tasks,
    measure_kneepoint,
    measure_per_sample_cost,
    plan_job,
    resolve_platform_config,
    wave_enabled,
)
from repro.platform.facade import JobReport, Platform  # noqa: F401
from repro.platform.monitor import (  # noqa: F401
    SLO,
    MonitorOptions,
    PlatformMonitor,
    SLOPolicy,
    TimeSeriesStore,
    render_monitor_report,
    resolve_monitor_options,
    write_alerts_jsonl,
    write_monitor_report,
)
from repro.platform.reduce import (  # noqa: F401
    StreamingReduceTree,
    finalize_stats,
    tree_add,
)
from repro.platform.service import (  # noqa: F401
    AdmissionError,
    AdmissionPolicy,
    CancelledError,
    DatasetHandle,
    JobTicket,
    PartialEstimate,
    PlatformService,
    QueryClass,
)
from repro.platform.telemetry import (  # noqa: F401
    EVENT_KINDS,
    Event,
    MetricsRegistry,
    TelemetryBus,
    TelemetryConfig,
    TelemetrySampler,
    build_trace,
    null_bus,
    render_report,
    resolve_telemetry_config,
    write_report,
    write_trace,
)

# The curated facade (ISSUE: stable public API).  Star-imports and API
# docs follow this list; additions are append-only.
__all__ = [
    # driver: one-shot jobs
    "Platform",
    "PlatformSpec",
    "JobReport",
    # grouped platform options
    "WaveOptions",
    "ScheduleOptions",
    "ApproxOptions",
    "FaultOptions",
    "CacheOptions",
    # multi-tenant service
    "PlatformService",
    "AdmissionPolicy",
    "DatasetHandle",
    "JobTicket",
    "PartialEstimate",
    # telemetry configuration
    "TelemetryConfig",
    # SLO monitor / critical-path / diagnosis (DESIGN.md §15)
    "MonitorOptions",
    "PlatformMonitor",
    "SLO",
]
