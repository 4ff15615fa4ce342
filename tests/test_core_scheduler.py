"""Two-phase scheduler tests: probe phase, dynamic queue depth, work
stealing, straggler behaviour, job- vs task-level recovery."""

import numpy as np

from tests._hypothesis_compat import given, settings, st

from repro.core.scheduler import (
    JobFailure,
    SchedulerConfig,
    SimParams,
    SimWorker,
    Task,
    TaskResult,
    TwoPhaseScheduler,
    simulate_job,
)


def mk_tasks(n, size=1.0):
    return [Task(i, (i,), size) for i in range(n)]


def uniform_params(exec_s=0.01, fetch_s=0.0, launch=0.0, startup=0.0):
    return SimParams(exec_time=lambda t: exec_s,
                     fetch_time=lambda t: fetch_s,
                     launch_overhead=launch, startup_time=startup)


def test_phase1_assigns_one_probe_task_per_worker():
    sched = TwoPhaseScheduler(4, mk_tasks(100))
    initial = sched.initial_assignments()
    assert len(initial) == 4
    assert sorted({w for w, _ in initial}) == [0, 1, 2, 3]


def test_queue_depth_grows_with_fetch_to_exec_ratio():
    sched = TwoPhaseScheduler(2, mk_tasks(10))
    sched._observe(TaskResult(0, 0, 0, fetch_time=0.10, exec_time=0.01))
    deep = sched.queue_depth()
    sched2 = TwoPhaseScheduler(2, mk_tasks(10))
    sched2._observe(TaskResult(0, 0, 0, fetch_time=0.001, exec_time=0.01))
    shallow = sched2.queue_depth()
    assert deep > shallow


def test_simulation_completes_all_tasks():
    workers = [SimWorker(i) for i in range(8)]
    out = simulate_job(mk_tasks(200), workers, uniform_params())
    assert len(out.results) == 200
    assert out.makespan > 0


def test_linear_scaling_with_workers():
    """Thesis Fig 12: throughput scales ~linearly for large jobs."""
    times = {}
    for n in (2, 4, 8):
        workers = [SimWorker(i) for i in range(n)]
        out = simulate_job(mk_tasks(512), workers, uniform_params())
        times[n] = out.makespan
    assert times[4] < 0.6 * times[2]
    assert times[8] < 0.6 * times[4]


def test_straggler_mitigation_large_jobs():
    """Thesis §4.2.4: slow node causes proportional slowdown on small jobs
    but is erased on large jobs (stealing + round-robin skipping)."""
    fast = [SimWorker(i) for i in range(5)]
    mixed = [SimWorker(i, speed=1.0 if i else 0.5) for i in range(5)]
    big = mk_tasks(1000)
    t_fast = simulate_job(big, fast, uniform_params()).makespan
    t_mixed = simulate_job(big, mixed, uniform_params()).makespan
    # one of five workers at half speed = 10% capacity loss; tiny tasks
    # should keep the impact close to the capacity loss, not 2x
    assert t_mixed < 1.35 * t_fast


def test_job_level_recovery_raises_and_restarts():
    workers = [SimWorker(i, fail_at=0.05 if i == 0 else None)
               for i in range(4)]
    out = simulate_job(mk_tasks(400), workers, uniform_params(),
                       SchedulerConfig(recovery="job"), max_restarts=3)
    # restarted at least once, and the retry (with the same failing worker
    # schedule) eventually completes because the failure time passes
    assert out.restarts >= 1
    assert len(out.results) == 400


def test_task_level_recovery_reclaims_and_finishes():
    workers = [SimWorker(i, fail_at=0.05 if i == 0 else None)
               for i in range(4)]
    out = simulate_job(mk_tasks(400), workers, uniform_params(),
                       SchedulerConfig(recovery="task"))
    assert out.restarts == 0
    done = {r.task_id for r in out.results}
    assert done == set(range(400))


def test_task_level_monitoring_costs_more_when_no_failures():
    workers = [SimWorker(i) for i in range(4)]
    tasks = mk_tasks(300)
    t_job = simulate_job(tasks, workers, uniform_params(),
                         SchedulerConfig(recovery="job")).makespan
    t_task = simulate_job(tasks, workers, uniform_params(),
                          SchedulerConfig(recovery="task",
                                          cost_tl=0.20)).makespan
    assert t_task > 1.15 * t_job


def test_prefetch_overlap_hides_fetch_time():
    """Warm queues overlap fetch with execution (thesis §3.5)."""
    workers = [SimWorker(i) for i in range(2)]
    with_fetch = simulate_job(mk_tasks(200), workers,
                              uniform_params(exec_s=0.01, fetch_s=0.008))
    no_fetch = simulate_job(mk_tasks(200), workers,
                            uniform_params(exec_s=0.01, fetch_s=0.0))
    # fetch ≤ exec ⇒ almost fully hidden
    assert with_fetch.makespan < 1.15 * no_fetch.makespan


@given(st.integers(min_value=1, max_value=16),
       st.integers(min_value=0, max_value=300),
       st.integers(min_value=0, max_value=10_000))
@settings(max_examples=40, deadline=None)
def test_simulation_conservation_property(n_workers, n_tasks, seed):
    """Every task completes exactly once, regardless of worker count."""
    rng = np.random.default_rng(seed)
    workers = [SimWorker(i, speed=float(rng.uniform(0.5, 2.0)))
               for i in range(n_workers)]
    params = SimParams(
        exec_time=lambda t: 0.001 + (t.task_id % 7) * 1e-4,
        fetch_time=lambda t: (t.task_id % 3) * 1e-4)
    out = simulate_job(mk_tasks(n_tasks), workers, params,
                       SchedulerConfig(seed=seed))
    ids = sorted(r.task_id for r in out.results)
    assert ids == list(range(n_tasks))
