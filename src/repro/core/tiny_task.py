"""Back-compat facade over :mod:`repro.platform` (thesis §4.1.3 configs).

The end-to-end tiny-task pipeline — kneepoint sizing, task partitioning,
scheduling, datastore fetch, streaming reduce — now lives in
``repro.platform`` (the Platform driver).  This module keeps the original
entry points (``PLATFORMS``, ``make_tasks``, ``run_subsampling_job``,
``measure_kneepoint``) so existing callers and tests keep working; new
code should use :class:`repro.platform.Platform` directly.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from repro.platform.compute import pad_to_common
from repro.platform.driver import (  # noqa: F401  (re-exported API)
    BASH_STARTUP,
    PLATFORMS,
    PlatformConfig,
    PlatformSpec,
    make_tasks,
    measure_kneepoint,
)
from repro.platform.facade import JobReport, Platform  # noqa: F401


def run_subsampling_job(
    samples: Dict[int, np.ndarray],
    months: Dict[int, np.ndarray],
    workload,
    *,
    platform: str = "BTS",
    n_workers: int = 4,
    knee_bytes: Optional[float] = None,
    datastore=None,
    seed: int = 0,
) -> JobReport:
    """Execute a subsampling job on the threaded backend (real wall time).

    Thin wrapper over :class:`repro.platform.Platform`; the offline
    kneepoint phase, if needed and not supplied, runs first and is charged
    to the report (thesis accounting: offline ≈ 3% of online).
    """
    spec = PlatformSpec(platform=platform, n_workers=n_workers,
                        backend="threaded", knee_bytes=knee_bytes,
                        seed=seed)
    return Platform(spec, datastore=datastore).run(samples, months, workload)


def _pad_to_common(arrays: List[np.ndarray]) -> List[np.ndarray]:
    """Deprecated alias — moved to ``repro.platform.compute.pad_to_common``."""
    return pad_to_common(arrays)
