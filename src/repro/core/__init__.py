"""The paper's primary contribution: tiny-task sizing (kneepoint), the
two-phase dynamic scheduler, the adaptive-replication data plane, prefetch
with dynamic look-ahead, job-level recovery, and the subsampling statistics
engine."""

from repro.core.kneepoint import (  # noqa: F401
    CurvePoint,
    KneepointResult,
    amat_curve,
    find_kneepoint,
    measure_curve,
    pack_tasks,
    timed_task,
)
from repro.core.scheduler import (  # noqa: F401
    JobFailure,
    SchedulerConfig,
    SimOutcome,
    SimParams,
    SimWorker,
    Task,
    TaskResult,
    TwoPhaseScheduler,
    simulate_job,
)
from repro.core.datastore import (  # noqa: F401
    DataNode,
    ReplicatedDataStore,
    ReplicationPolicy,
)
from repro.core.estimator import (  # noqa: F401
    EstimateSnapshot,
    ReplayStopper,
    StoppingController,
    SubsampleEstimator,
    normal_ppf,
    z_for_confidence,
)
from repro.core.prefetch import PrefetchPipeline  # noqa: F401
from repro.core.recovery import (  # noqa: F401
    JobRunner,
    decide_policy,
    expected_failures,
    min_cluster_for_task_level,
    recovery_overhead_budget,
)
from repro.core import subsample  # noqa: F401
from repro.core import slo  # noqa: F401

# NOTE: repro.core.tiny_task is intentionally NOT imported here — it is a
# facade over repro.platform (which itself imports repro.core); importing
# it eagerly would create a package-level cycle.  `from repro.core import
# tiny_task` still works as a plain submodule import.
