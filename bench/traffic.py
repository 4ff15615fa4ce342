"""The one traffic generator: a traffic file's parameters and ``--seed``
make a plan, and the plan drives the service's served path,
``PlatformService.submit(handle, workload, seed=...).result()``.

Arrivals (the file's ``arrivals``):

* ``closed``: ``clients`` threads, each submitting its next job when its
  last one has returned, until the window closes; a job's latency runs
  from its submit to its finish.
* ``poisson``: one open-loop generator submits on a schedule, whatever
  the service does.  The schedule's gaps are one fixed set (drawn once
  from the file's ``schedule_seed`` at ``rate_hz``) in an order drawn
  from ``--seed``, so every seed offers the same jobs in the same window.
  A job's latency runs from the time it was due, so a late generator
  shows; how late it ran is reported.

Job seeds come from ``--seed``; a job's tasks draw with ``seed + task``,
so seeds stay below ``2**30``.  The benchmark's spans (``bench.*``) mark
what the client was doing, for the trace.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, List, Optional

import numpy as np

# a job that has not finished this long after the window closed never
# comes
GRACE_S = 60.0
MAX_JOBS = 1 << 16


@dataclasses.dataclass
class Job:
    index: int
    seed: int
    due: float                        # time.monotonic()
    ticket: Any = None
    result: Optional[dict] = None
    error: Optional[str] = None

    @property
    def latency_s(self) -> float:
        return self.ticket.finished_at - self.due


@dataclasses.dataclass
class Plan:
    arrivals: str
    seeds: np.ndarray
    clients: int = 0
    offsets: Optional[np.ndarray] = None   # due times after the start


@dataclasses.dataclass
class Window:
    jobs: List[Job]
    seconds: float                    # start to the last finish
    lateness: List[float]


def poisson_offsets(rate_hz: float, seconds: float, schedule_seed: int,
                    seed: int) -> np.ndarray:
    """Due times in ``[0, seconds)``: the fixed gaps in ``seed``'s order."""
    rng = np.random.default_rng(schedule_seed)
    gaps = rng.exponential(1.0 / rate_hz, int(rate_hz * seconds * 2) + 16)
    n = int(np.searchsorted(np.cumsum(gaps), seconds))
    gaps = np.random.default_rng(seed).permutation(gaps[:n])
    return np.cumsum(gaps)


def plan(mix: dict, seconds: float, seed: int) -> Plan:
    seeds = np.random.default_rng(seed).integers(0, 1 << 30, MAX_JOBS)
    if mix["arrivals"] == "closed":
        return Plan("closed", seeds, clients=mix["clients"])
    if mix["arrivals"] == "poisson":
        return Plan("poisson", seeds, offsets=poisson_offsets(
            mix["rate_hz"], seconds, mix["schedule_seed"], seed))
    raise ValueError(f"unknown arrivals {mix['arrivals']!r}")


def _span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def _submit(svc, handle, wl, job: Job) -> None:
    with _span("bench.submit"):
        try:
            job.ticket = svc.submit(handle, wl, seed=job.seed)
        except Exception as e:             # noqa: BLE001  a refused job
            job.error = repr(e)


def _finish(job: Job, deadline: float) -> None:
    if job.ticket is None:
        return
    try:
        job.result = job.ticket.result(
            timeout=max(0.0, deadline - time.monotonic()))
    except Exception as e:                 # noqa: BLE001  a failed job
        job.error = repr(e)


def _closed(svc, handle, wl, plan: Plan, seconds: float) -> List[Job]:
    start = time.monotonic()
    close, deadline = start + seconds, start + seconds + GRACE_S
    jobs: List[Job] = []
    lock = threading.Lock()

    def client():
        while True:
            now = time.monotonic()
            if now >= close:
                return
            with lock:
                job = Job(len(jobs), int(plan.seeds[len(jobs)]), now)
                jobs.append(job)
            _submit(svc, handle, wl, job)
            with _span("bench.result_wait"):
                _finish(job, deadline)

    threads = [threading.Thread(target=client, name=f"bench-client-{i}")
               for i in range(plan.clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(seconds + 2 * GRACE_S)
    return jobs


def _open(svc, handle, wl, plan: Plan, seconds: float, lateness: list
          ) -> List[Job]:
    start = time.monotonic()
    deadline = start + seconds + GRACE_S
    jobs: List[Job] = []
    for k, offset in enumerate(plan.offsets):
        due = start + float(offset)
        with _span("bench.arrival_wait"):
            pause = due - time.monotonic()
            if pause > 0:
                time.sleep(pause)
        job = Job(k, int(plan.seeds[k]), due)
        lateness.append(time.monotonic() - due)
        _submit(svc, handle, wl, job)
        jobs.append(job)
    with _span("bench.result_wait"):
        for job in jobs:
            _finish(job, deadline)
    return jobs


def drive(svc, handle, wl, plan: Plan, seconds: float) -> Window:
    lateness: List[float] = []
    start = time.monotonic()
    with _span("bench.window"):
        if plan.arrivals == "closed":
            jobs = _closed(svc, handle, wl, plan, seconds)
        else:
            jobs = _open(svc, handle, wl, plan, seconds, lateness)
    finished = [j.ticket.finished_at for j in jobs
                if j.ticket is not None and j.ticket.finished_at is not None]
    end = max(finished) if finished else time.monotonic()
    return Window(jobs, end - start, lateness)
