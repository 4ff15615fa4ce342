"""Balanced dynamic scheduling tests (ISSUE 4): response-time-ranked
claims, straggler speculation with first-completion-wins bit-identity,
degraded-node failover, dynamic-k prefetch, and the recovery/SLO
cost-model units."""

import time

import numpy as np

from repro.core import recovery, slo
from repro.core.datastore import (
    DEGRADED,
    DOWN,
    HEALTHY,
    DataNode,
    DataNodeError,
    ReplicatedDataStore,
    ReplicationPolicy,
)
from repro.core.prefetch import TaskPrefetcher
from repro.core.scheduler import (
    MultiJobConfig,
    MultiJobScheduler,
    SchedulerConfig,
    SimParams,
    SimWorker,
    Task,
    TaskResult,
    TwoPhaseScheduler,
    simulate_job,
)
from repro.platform import Platform, PlatformService, PlatformSpec
from repro.platform.compute import MomentsSpec
from repro.platform.faults import FaultEvent, FaultInjector, FaultPlan

WL = MomentsSpec(draws=4, draw_size=16)


def _dataset(n=24, length=32, seed=0):
    rng = np.random.default_rng(seed)
    samples = {i: rng.standard_normal(length).astype(np.float32)
               for i in range(n)}
    months = {i: np.zeros(length, np.int32) for i in range(n)}
    return samples, months


def _store(n_nodes=3, latency=1e-4, **policy_kw):
    policy = ReplicationPolicy(window=10_000, max_replicas=n_nodes,
                               **policy_kw)
    return ReplicatedDataStore(n_initial=n_nodes, policy=policy,
                               latency=lambda nbytes: latency)


def _spec(**kw):
    base = dict(platform="BTS", n_workers=2, backend="threaded",
                engine="numpy", knee_bytes=4 * 32 * 4, seed=0,
                startup_time=0.0)
    base.update(kw)
    return PlatformSpec(**base)


# -- datastore: scoring + availability ---------------------------------------


def test_node_scores_reflect_response_times():
    store = _store()
    store.nodes[0].latency = lambda nbytes: 5e-3
    store.put_all({i: np.zeros(16, np.float32) for i in range(6)})
    store.probe()
    scores = store.node_scores()
    assert scores[0] > 3 * scores[1]
    assert scores[0] > 3 * scores[2]


def test_latency_outlier_marks_node_degraded():
    store = _store()
    store.nodes[0].latency = lambda nbytes: 8e-3   # ≫ degraded_factor·peers
    store.put_all({i: np.zeros(16, np.float32) for i in range(6)})
    events = []
    store.on_state_change = lambda node: events.append(
        (node.node_id, node.state))
    store.probe()
    for i in range(12):                            # peers build their EMAs
        store.fetch(i % 6)
    assert store.node_states()[0] == DEGRADED
    assert (0, DEGRADED) in events


def test_consecutive_failures_take_node_down_with_failover():
    """Satellite regression: a raising DataNode.fetch must NOT be
    retried forever on the same replica — bounded retries fail over and
    the node goes DOWN."""
    store = _store()
    data = {i: np.full(8, i, np.float32) for i in range(6)}
    store.put_all(data)
    store.nodes[0].failing = True
    # every fetch still succeeds (served by a surviving replica) …
    for i in range(12):
        np.testing.assert_array_equal(store.fetch(i % 6), data[i % 6])
    # … and the failing node is out of the replica set after the bound
    assert store.node_states()[0] == DOWN
    assert store.nodes[0].failures >= store.policy.max_consecutive_failures
    # DOWN nodes never serve claims again
    before = store.nodes[0].failures
    for i in range(6):
        store.fetch(i)
    assert store.nodes[0].failures == before


def test_fetch_raises_when_every_replica_down():
    store = _store(n_nodes=2)
    store.put_all({0: np.zeros(4, np.float32)})
    for node in store.nodes:
        node.failing = True
    try:
        store.fetch(0)
        raise AssertionError("expected DataNodeError")
    except DataNodeError:
        pass


def test_fetch_many_fails_over_mid_batch():
    store = _store()
    data = {i: np.full(8, i, np.float32) for i in range(9)}
    store.put_all(data)
    store.nodes[1].failing = True
    out = store.fetch_many(list(range(9)))
    for i, arr in enumerate(out):
        np.testing.assert_array_equal(arr, data[i])


def test_sharded_placement_and_task_scores():
    store = _store()
    data = {i: np.full(8, i, np.float32) for i in range(9)}
    store.put_all(data, replication=2)
    for sid in data:
        assert len(store.replicas_of(sid)) == 2
        np.testing.assert_array_equal(store.fetch(sid), data[sid])
    # a task whose every sample lost all replicas scores ∞
    only_on = [sid for sid in data
               if set(store.replicas_of(sid)) == {0, 1}]
    store.mark_down(0)
    store.mark_down(1)
    assert store.predicted_task_fetch(only_on) == float("inf")
    store.revive(0)
    assert store.node_states()[0] == HEALTHY
    assert store.predicted_task_fetch(only_on) < float("inf")


def test_put_all_reput_preserves_sharded_placement():
    """The driver re-puts the dataset on every run; that must refresh
    bytes on the existing holders, never widen replication-k placement
    into full replication."""
    store = _store()
    data = {i: np.full(8, i, np.float32) for i in range(6)}
    store.put_all(data, replication=2)
    before = {sid: store.replicas_of(sid) for sid in data}
    store.put_all(data)                        # the driver's re-put
    assert {sid: store.replicas_of(sid) for sid in data} == before
    # an explicit replication re-places and frees dropped holders
    store.put_all(data, replication=1)
    assert all(len(store.replicas_of(sid)) == 1 for sid in data)
    held = sum(sid in n.store for n in store.nodes for sid in data)
    assert held == len(data)


def test_balanced_on_requires_datastore():
    samples, months = _dataset(n=8)
    try:
        Platform(_spec(balanced="on")).run(samples, months, WL)
        raise AssertionError("expected ValueError")
    except ValueError:
        pass
    try:
        PlatformService(_spec(balanced="on"))
        raise AssertionError("expected ValueError")
    except ValueError:
        pass


# -- response-time-ranked claims ---------------------------------------------


def _bucketed_tasks():
    # two shape buckets interleaved: even tasks bucket A, odd bucket B
    return [Task(i, (i,), 1.0, payload="A" if i % 2 == 0 else "B")
            for i in range(8)]


def test_two_phase_ranking_moves_cheap_bucket_first_keeping_fifo():
    tasks = _bucketed_tasks()
    score = {"A": 5.0, "B": 1.0}
    sched = TwoPhaseScheduler(
        1, tasks, SchedulerConfig(),
        locality_score=lambda t: score[t.payload],
        bucket_key=lambda t: t.payload)
    order = [t.task_id for t in sched.backlog]
    assert order == [1, 3, 5, 7, 0, 2, 4, 6]   # B first, FIFO inside


def test_platform_run_reranks_when_a_node_goes_down_mid_job(monkeypatch):
    """A data node lost mid-job re-ranks the ready queue through the
    pool's scheduler, and the answer is unchanged."""
    samples, months = _dataset(n=48)
    clean = Platform(_spec()).run(samples, months, WL)
    requested = []
    real = MultiJobScheduler.request_rerank

    def spy(self):
        requested.append(self)
        real(self)

    monkeypatch.setattr(MultiJobScheduler, "request_rerank", spy)
    store = _store()
    inj = FaultInjector(FaultPlan(events=[
        FaultEvent(kind="node_kill", target=0, at_completions=2)]))
    rep = Platform(_spec(balanced="on"), datastore=store,
                   fault_injector=inj).run(samples, months, WL)
    assert store.node_states()[0] == DOWN
    assert requested
    assert requested[0].reranks >= 2        # at admission, then again
    for key in clean.result:
        np.testing.assert_array_equal(np.asarray(clean.result[key]),
                                      np.asarray(rep.result[key]))


def test_prefetch_on_requires_datastore_and_threaded_backend():
    samples, months = _dataset(n=8)
    for bad in (dict(prefetch="on"),
                dict(prefetch="on", backend="simulated")):
        try:
            Platform(_spec(**bad), datastore=(
                _store() if bad.get("backend") else None)).run(
                samples, months, WL)
            raise AssertionError("expected ValueError")
        except ValueError:
            pass


def test_peek_matches_claim_order_across_priorities():
    sched = MultiJobScheduler(2)
    sched.add_job(0, [Task(0, (0,), 1.0)], priority=0)
    sched.add_job(1, [Task(1, (1,), 1.0)], priority=5)
    peeked = sched.peek(1)
    claimed = sched.claim(now=0.0)
    assert peeked[0][1].task_id == claimed[0][1].task_id == 1


def test_multi_job_ranking_keeps_fuse_buckets_contiguous():
    score = {"A": 9.0, "B": 2.0}
    sched = MultiJobScheduler(2)
    sched.add_job(0, _bucketed_tasks(), fuse_key=lambda t: t.payload,
                  cap=4, locality_score=lambda t: score[t.payload])
    batch = sched.claim(now=0.0)
    assert [t.payload for _, t in batch] == ["B"] * 4   # whole bucket fused


# -- straggler speculation ----------------------------------------------------


def test_should_speculate_cost_model():
    # not a straggler yet
    assert not recovery.should_speculate(1.5, 1.0, straggler_factor=2.0)
    # straggler AND the gain beats the clone tax
    assert recovery.should_speculate(3.0, 1.0, straggler_factor=2.0)
    # no EMA ⇒ never speculate
    assert not recovery.should_speculate(10.0, None)
    assert not recovery.should_speculate(10.0, 0.0)


def test_sim_speculation_first_completion_wins_and_helps():
    tasks = [Task(i, (i,), 1.0) for i in range(64)]
    workers = [SimWorker(i, speed=0.1 if i == 0 else 1.0)
               for i in range(4)]
    params = SimParams(exec_time=lambda t: 2e-3, fetch_time=lambda t: 2e-4)
    off = simulate_job(tasks, workers, params,
                       SchedulerConfig(speculative=False))
    on = simulate_job(tasks, workers, params,
                      SchedulerConfig(speculative="auto"))
    assert on.speculative_launches >= 1
    assert on.speculation_wins >= 1
    assert on.makespan < off.makespan
    # every task completed exactly once (duplicates discarded)
    assert sorted(r.task_id for r in on.results) == list(range(64))


def test_speculation_bit_identity_threaded_and_simulated():
    samples, months = _dataset()
    base = Platform(_spec(speculation="off")).run(samples, months, WL)
    for backend in ("threaded", "simulated"):
        rep = Platform(_spec(backend=backend, speculation="on",
                             straggler_factor=1.5)).run(samples, months, WL)
        for key in base.result:
            np.testing.assert_array_equal(
                np.asarray(base.result[key]), np.asarray(rep.result[key]),
                err_msg=f"{backend} speculation drifted on {key!r}")


def test_multi_job_speculative_clone_once_and_settles():
    sched = MultiJobScheduler(2, MultiJobConfig(speculative="auto",
                                                straggler_factor=2.0))
    sched.add_job(0, [Task(0, (0,), 1.0)])
    batch = sched.claim(now=0.0)
    assert len(batch) == 1
    sched.avg_task_seconds = 0.1
    clones = sched.claim_speculative(now=10.0)
    assert len(clones) == 1 and clones[0][1].task_id == 0
    assert sched.claim_speculative(now=20.0) == []   # cloned at most once
    job = sched.jobs[0]
    assert job.inflight == 2
    # the ORIGINAL completes first: the job finishes, but the race was
    # lost by the clone — no win is recorded
    assert sched.on_task_complete(0, 0.1, 0)
    assert sched.speculation_wins == 0
    # duplicate settles in-flight accounting without double counting:
    # the job already completed and left the table
    assert not sched.on_task_complete(0, 0.1, 0, speculative=True)
    assert sched.speculation_wins == 0


def test_failed_clone_abandoned_without_failing_job():
    """A clone is a redundant bet: its failure settles accounting and
    leaves the job (and the racing original) untouched."""
    sched = MultiJobScheduler(2, MultiJobConfig(speculative=True))
    sched.add_job(0, [Task(0, (0,), 1.0)])
    sched.claim(now=0.0)
    sched.avg_task_seconds = 0.1
    assert len(sched.claim_speculative(now=10.0)) == 1
    sched.on_task_abandoned(0, 0)              # clone execution failed
    assert 0 in sched.jobs                     # job unaffected
    assert sched.jobs[0].inflight == 1
    assert sched.on_task_complete(0, 0.1, 0)   # original completes it
    assert sched.speculation_wins == 0


def test_multi_job_speculation_win_counts_clone_first():
    sched = MultiJobScheduler(2, MultiJobConfig(speculative=True,
                                                straggler_factor=2.0))
    sched.add_job(0, [Task(0, (0,), 1.0)])
    sched.claim(now=0.0)
    sched.avg_task_seconds = 0.1
    assert len(sched.claim_speculative(now=10.0)) == 1
    # the CLONE completes first: that IS a win
    assert sched.on_task_complete(0, 0.1, 0, speculative=True)
    assert sched.speculation_wins == 1
    assert not sched.on_task_complete(0, 0.1, 0)     # original settles


# -- prefetch pipeline --------------------------------------------------------


def test_task_prefetcher_dynamic_k_adapts():
    pf = TaskPrefetcher(min_depth=1, max_depth=16, workers=2)
    assert pf.lookahead() == 1                 # no EMAs yet
    pf._observe_fetch(50e-3)
    pf.observe_exec(1e-3)
    assert pf.lookahead() == 16                # fetch ≫ exec ⇒ deep
    pf.observe_exec(100e-3)
    for _ in range(30):                        # EMA converges upward
        pf.observe_exec(100e-3)
    assert pf.lookahead() <= 2                 # exec ≫ fetch ⇒ shallow
    pf.close()


def test_task_prefetcher_hit_miss_accounting():
    pf = TaskPrefetcher(min_depth=4, max_depth=8, workers=2)
    fetched = []

    def mk(k):
        return lambda: fetched.append(k) or k

    launched = pf.prefetch([(0, mk(0)), (1, mk(1))])
    assert launched == 2
    assert pf.ensure(0, mk(0)) == 0            # served by the prefetch
    assert pf.ensure(7, mk(7)) == 7            # miss: fetched inline
    assert pf.hits == 1 and pf.misses == 1
    assert fetched.count(0) == 1               # never fetched twice
    pf.close()


def test_prefetch_preserves_bit_identity_with_datastore():
    samples, months = _dataset()
    store_off = _store()
    off = Platform(_spec(prefetch="off", balanced="off"),
                   datastore=store_off).run(samples, months, WL)
    store_on = _store()
    on = Platform(_spec(prefetch="on", balanced="on"),
                  datastore=store_on).run(samples, months, WL)
    for key in off.result:
        np.testing.assert_array_equal(
            np.asarray(off.result[key]), np.asarray(on.result[key]))
    assert on.prefetch_stats is not None
    assert on.prefetch_stats["prefetch_hits"] > 0


# -- recovery / SLO integration units ----------------------------------------


def test_expected_failures_matches_thesis_numbers():
    f_w = recovery.expected_failures(**recovery.THESIS_DEFAULTS)
    assert abs(f_w - 0.0078) < 5e-4            # §3.3: ≈ 0.78%
    assert recovery.decide_policy(**recovery.THESIS_DEFAULTS,
                                  cost_tl=0.20) == "job"


def test_choose_workers_prefers_fewer_cores_under_tight_slo():
    tight = slo.choose_workers(16, bytes_per_second_per_worker=1e6,
                               startup_seconds=2.0, slo_seconds=2.5)
    loose = slo.choose_workers(16, bytes_per_second_per_worker=1e6,
                               startup_seconds=0.01, slo_seconds=60.0)
    assert tight.cores <= loose.cores
    assert loose.cores >= 8


def test_driver_slo_sizing_sets_scale_decision():
    samples, months = _dataset()
    spec = _spec(n_workers=8, knee_bytes=None, task_sizing="kneepoint",
                 slo_seconds=30.0)
    rep = Platform(spec).run(samples, months, WL)
    assert rep.scale_decision is not None
    assert 1 <= rep.n_workers_used <= 8


# -- end-to-end: degraded node through driver and service ---------------------


def test_degraded_node_failover_bit_identity_threaded():
    samples, months = _dataset()
    clean = Platform(_spec()).run(samples, months, WL)
    store = _store(latency=1e-3)
    store.nodes[0].failing = True              # hard-down, not just slow
    rep = Platform(_spec(balanced="on", prefetch="on"),
                   datastore=store).run(samples, months, WL)
    for key in clean.result:
        np.testing.assert_array_equal(
            np.asarray(clean.result[key]), np.asarray(rep.result[key]))
    assert store.node_states()[0] == DOWN


def test_service_balanced_submit_matches_platform_run():
    samples, months = _dataset()
    clean = Platform(_spec()).run(samples, months, WL)
    store = _store(latency=1e-3)
    store.nodes[0].latency = lambda nbytes: 5e-3   # 5x degraded replica
    with PlatformService(_spec(balanced="on", prefetch="on",
                               speculation="auto"),
                         datastore=store) as svc:
        handle = svc.register_dataset(samples, months)
        ticket = svc.submit(handle, WL)
        result = ticket.result(timeout=120.0)
    for key in clean.result:
        np.testing.assert_array_equal(
            np.asarray(clean.result[key]), np.asarray(result[key]))
    assert store.node_states()[0] in (DEGRADED, HEALTHY)
    scores = store.node_scores()
    assert scores[0] > scores[1]               # degraded node scores worst
