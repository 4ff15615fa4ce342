"""Fault-injection framework + recovery-layer tests (DESIGN.md §12):
seeded FaultPlan determinism, lease-based reclamation in the pool's
scheduler, worker-crash respawn bit-identity and checkpoint resume
through ``Platform.run`` and the service, probe-driven datastore
auto-revival, the unified RetryPolicy, and checkpoint error surfacing.
"""

import os
import threading
import time

import numpy as np
import pytest

from repro.core import recovery as rec
from repro.core.datastore import (
    DEGRADED,
    DOWN,
    HEALTHY,
    DataNodeError,
    ReplicatedDataStore,
    ReplicationPolicy,
)
from repro.core import subsample as ss
from repro.core.scheduler import (
    MultiJobConfig,
    MultiJobScheduler,
    Task,
)
from repro.data.synthetic import NetflixSpec, netflix_dataset
from repro.platform import FaultOptions, Platform, PlatformSpec
from repro.platform.faults import (
    FaultEvent,
    FaultInjector,
    FaultPlan,
    InjectedCrash,
)


def mk_tasks(n, size=1.0):
    return [Task(i, (i,), size) for i in range(n)]


# ---------------------------------------------------------------------------
# FaultPlan / FaultInjector
# ---------------------------------------------------------------------------


def test_fault_plan_from_seed_is_deterministic():
    kw = dict(n_workers=4, n_nodes=3, n_tasks=16,
              worker_crashes=2, node_kills=1, latency_spikes=1,
              revive_after=2)
    a = FaultPlan.from_seed(7, **kw)
    b = FaultPlan.from_seed(7, **kw)
    assert a.events == b.events
    c = FaultPlan.from_seed(8, **kw)
    assert a.events != c.events


def test_fault_event_rejects_unknown_kind():
    with pytest.raises(ValueError):
        FaultEvent(kind="meteor_strike")


def test_node_event_fires_at_exact_completion_count():
    plan = FaultPlan(events=[
        FaultEvent(kind="node_kill", target=0, at_completions=3)])
    inj = FaultInjector(plan)
    store = ReplicatedDataStore(2, seed=0)
    store.put_all({0: np.zeros(4, dtype=np.float32)})
    inj.attach_store(store)
    emit = inj.wrap_emit(None)
    emit(0, None)
    emit(1, None)
    assert inj.fired == []
    emit(2, None)                       # third completion: due
    assert [e.kind for e in inj.fired] == ["node_kill"]
    assert store.node_states()[0] == DOWN


def test_worker_tick_raises_once_at_kth_claim():
    plan = FaultPlan(events=[
        FaultEvent(kind="worker_crash", target=1, at_claims=2)])
    inj = FaultInjector(plan)
    inj.worker_tick(0)                  # other worker: never fires
    inj.worker_tick(1)                  # claim 1 of target: not yet
    with pytest.raises(rec.WorkerCrash):
        inj.worker_tick(1)              # claim 2: fires
    inj.worker_tick(1)                  # once only — respawned id is safe
    assert len(inj.fired) == 1


def test_checkpoint_tick_raises_once_at_kth_save():
    plan = FaultPlan(events=[
        FaultEvent(kind="checkpoint_crash", at_saves=2)])
    inj = FaultInjector(plan)
    inj.checkpoint_tick()
    with pytest.raises(InjectedCrash):
        inj.checkpoint_tick()
    inj.checkpoint_tick()               # fired state is per-event
    assert inj.stats()["events_pending"] == 0.0


def test_node_latency_spike_and_revive_restore_latency_model():
    plan = FaultPlan(events=[
        FaultEvent(kind="node_latency", target=0, at_completions=1,
                   factor=4.0),
        FaultEvent(kind="node_revive", target=0, at_completions=2)])
    inj = FaultInjector(plan)
    store = ReplicatedDataStore(2, latency=lambda nbytes: 1e-4, seed=0)
    inj.attach_store(store)
    orig = store.nodes[0].latency
    inj.on_progress(1)
    assert store.nodes[0].latency(100) == pytest.approx(4 * orig(100))
    inj.on_progress(1)
    assert store.nodes[0].latency is orig


# ---------------------------------------------------------------------------
# MultiJobScheduler: dead workers, leases, lost tasks
# ---------------------------------------------------------------------------


def _mjs(n_tasks=6, lease=None):
    sched = MultiJobScheduler(2, MultiJobConfig(lease_seconds=lease))
    # uniform fuse key so one claim can batch several tasks
    sched.add_job(0, mk_tasks(n_tasks), cap=4, fuse_key=lambda t: 0)
    return sched


def test_multi_job_on_worker_dead_requeues():
    sched = _mjs()
    batch = sched.claim(now=0.0, max_n=2, worker=0)
    assert len(batch) == 2
    lost = sched.on_worker_dead(0)
    assert len(lost) == 2
    job = sched.jobs[0]
    assert job.inflight == 0
    # requeued at the front, claimable by a peer
    again = sched.claim(now=0.0, max_n=2, worker=1)
    assert {t.task_id for _, t in again} == {t.task_id for _, t in batch}
    assert sched.on_worker_dead(0) == []  # idempotent


def test_multi_job_lease_expiry_requeues_then_dedups():
    sched = _mjs(lease=0.01)
    (job, task), = sched.claim(now=0.0, max_n=1, worker=0)
    assert sched.reclaim_expired(now=0.005) == []
    expired = sched.reclaim_expired(now=0.02)
    assert [(j, t.task_id) for j, t in expired] == [(job.job_id,
                                                     task.task_id)]
    # original settles first; the requeued duplicate is filtered at
    # claim time and the job still finishes exactly once
    sched.on_task_complete(job.job_id, 0.01, task.task_id, worker=0)
    assert task.task_id in job.completed_ids
    assert job.completed == 1


def test_multi_job_on_task_lost_shrinks_job():
    sched = _mjs(n_tasks=3)
    (job, task), = sched.claim(now=0.0, max_n=1, worker=0)
    finished = sched.on_task_lost(job.job_id, task.task_id, worker=0)
    assert not finished                  # two tasks still pending
    assert job.n_tasks == 2
    assert job.inflight == 0


# ---------------------------------------------------------------------------
# Platform.run: crash respawn, lease reclamation and resume on the pool
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def netflix12():
    return netflix_dataset(NetflixSpec(n_movies=12, mean_ratings=512))


def _platform_spec(**kw):
    base = dict(platform="BTS", n_workers=3, backend="threaded",
                knee_bytes=512 * 4, seed=5)
    base.update(kw)
    return PlatformSpec(**base)


def _same(a, b):
    assert set(a) == set(b)
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))


@pytest.mark.parametrize("targets", [(1,), (0, 2)],
                         ids=["one_crash", "two_crashes"])
def test_platform_run_crash_respawn_bit_identical(netflix12, targets):
    samples, months = netflix12
    clean = Platform(_platform_spec()).run(samples, months, ss.NETFLIX_HIGH)
    inj = FaultInjector(FaultPlan(events=tuple(
        FaultEvent(kind="worker_crash", target=w, at_claims=1)
        for w in targets)))
    rep = Platform(_platform_spec(), fault_injector=inj).run(
        samples, months, ss.NETFLIX_HIGH)
    assert [e.kind for e in inj.fired] == ["worker_crash"] * len(targets)
    assert rep.restarts == len(targets)
    assert rep.fault_events == len(targets)
    _same(clean.result, rep.result)


def test_platform_run_crash_without_respawn_shrinks_pool(netflix12):
    samples, months = netflix12
    clean = Platform(_platform_spec()).run(samples, months, ss.NETFLIX_HIGH)
    inj = FaultInjector(FaultPlan(events=[
        FaultEvent(kind="worker_crash", target=0, at_claims=1)]))
    rep = Platform(_platform_spec(faults=FaultOptions(max_respawns=0)),
                   fault_injector=inj).run(samples, months, ss.NETFLIX_HIGH)
    assert [e.kind for e in inj.fired] == ["worker_crash"]
    assert rep.restarts == 0            # the survivors finish the job
    assert rep.tasks_executed == rep.n_tasks
    _same(clean.result, rep.result)


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_platform_run_worker_death_between_claim_and_settlement(netflix12):
    """A worker that dies holding its claim, without reporting, under a
    lease: the job still completes, bit-identical to a clean run."""
    samples, months = netflix12
    spec = _platform_spec(faults=FaultOptions(lease_seconds=0.5))
    clean = Platform(spec).run(samples, months, ss.NETFLIX_HIGH)
    died = threading.Event()

    class DeathInjector:
        """Only the crash hook matters: worker 0 dies at its first claim
        with an unexpected error, not a reported WorkerCrash."""

        fired: list = []

        def worker_tick(self, wid):
            if wid == 0 and not died.is_set():
                died.set()
                raise RuntimeError("thread dies silently")

        def wrap_emit(self, emit):
            return emit

        def attach_store(self, store):
            pass

    rep = Platform(spec, fault_injector=DeathInjector()).run(
        samples, months, ss.NETFLIX_HIGH)
    assert died.is_set()
    assert rep.restarts == 1
    _same(clean.result, rep.result)


def test_platform_run_resumes_after_checkpoint_crash(netflix12, tmp_path):
    """The benchmarks' resume scenario: a checkpoint-write crash at the
    second save, then a run with ``resume_from`` restores the committed
    partials, executes only the missing tasks, and is bit-identical."""
    samples, months = netflix12
    clean = Platform(_platform_spec()).run(samples, months, ss.NETFLIX_HIGH)
    ckdir = str(tmp_path / "ck")
    crash = FaultInjector(FaultPlan(events=[
        FaultEvent(kind="checkpoint_crash", at_saves=2)]))
    with pytest.raises(InjectedCrash):
        Platform(_platform_spec(faults=FaultOptions(
            checkpoint_dir=ckdir, checkpoint_every=3)),
            fault_injector=crash).run(samples, months, ss.NETFLIX_HIGH)
    p = Platform(_platform_spec())
    resumed = p.run(samples, months, ss.NETFLIX_HIGH, resume_from=ckdir)
    assert 0 < resumed.tasks_restored < resumed.n_tasks
    # the pool settled exactly the tasks the checkpoint did not hold
    settled = p.telemetry.metrics.snapshot()["counters"]["tasks_settled"]
    assert settled == resumed.n_tasks - resumed.tasks_restored
    _same(clean.result, resumed.result)


# ---------------------------------------------------------------------------
# Datastore: probe-driven auto-revival
# ---------------------------------------------------------------------------


def _down_node(store, nid=0):
    """Drive node ``nid`` DOWN through the failure detector (arming the
    auto-revival probe — unlike administrative mark_down)."""
    store.nodes[nid].failing = True
    for _ in range(store.policy.max_consecutive_failures):
        for sid in store._samples:
            try:
                store.fetch(sid)
            except DataNodeError:
                pass
            if store.node_states()[nid] == DOWN:
                return
    assert store.node_states()[nid] == DOWN


def test_auto_revival_probe_restores_recovered_node():
    policy = ReplicationPolicy(probe_interval=0.01)
    store = ReplicatedDataStore(2, policy=policy, seed=0)
    store.put_all({i: np.zeros(8, dtype=np.float32) for i in range(4)})
    _down_node(store, 0)
    node = store.nodes[0]
    assert node.auto_probe and node.next_probe_at is not None
    node.failing = False                # the node "comes back"
    time.sleep(0.02)
    store.fetch(0)                      # fetch path runs the due probe
    # back in service: revive() sets HEALTHY, but the probe's own
    # latency seeds the fresh EMA and on a loaded machine can land
    # above the peer-median outlier threshold — DEGRADED still serves
    # claims, only DOWN is out of rotation
    assert store.node_states()[0] in (HEALTHY, DEGRADED)
    assert not node.auto_probe          # probe disarmed after revival


def test_failed_probe_backs_off_and_leaves_node_down():
    policy = ReplicationPolicy(probe_interval=0.01,
                               probe_backoff_factor=2.0)
    store = ReplicatedDataStore(2, policy=policy, seed=0)
    store.put_all({i: np.zeros(8, dtype=np.float32) for i in range(4)})
    _down_node(store, 0)
    node = store.nodes[0]
    failures_before = node.failures
    time.sleep(0.02)
    store.fetch(0)                      # probe runs, node still failing
    assert store.node_states()[0] == DOWN
    assert node.probe_interval == pytest.approx(0.02)
    # probes are health checks, not serving failures: the availability
    # counters don't move (pinned by the balanced-scheduling tests too)
    assert node.failures == failures_before


def test_administrative_mark_down_is_sticky():
    policy = ReplicationPolicy(probe_interval=0.01)
    store = ReplicatedDataStore(2, policy=policy, seed=0)
    store.put_all({i: np.zeros(8, dtype=np.float32) for i in range(4)})
    store.mark_down(0)
    assert store.nodes[0].auto_probe is False
    time.sleep(0.02)
    store.fetch(0)
    assert store.node_states()[0] == DOWN


# ---------------------------------------------------------------------------
# RetryPolicy / RetryBudget
# ---------------------------------------------------------------------------


def test_retry_policy_retries_transient_then_succeeds():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError("flake")
        return "ok"

    policy = rec.RetryPolicy(max_attempts=3)
    assert policy.call(flaky) == "ok"
    assert len(calls) == 3


def test_retry_policy_fails_fast_on_permanent():
    calls = []

    def broken():
        calls.append(1)
        raise KeyError("missing")

    with pytest.raises(KeyError):
        rec.RetryPolicy(max_attempts=5).call(broken)
    assert len(calls) == 1

    def tagged():
        calls.append(1)
        e = OSError("replicas exhausted")
        e.permanent = True
        raise e

    calls.clear()
    with pytest.raises(OSError):
        rec.RetryPolicy(max_attempts=5).call(tagged)
    assert len(calls) == 1


def test_retry_budget_exhaustion_stops_retrying():
    budget = rec.RetryBudget(limit=1)
    calls = []

    def flaky():
        calls.append(1)
        raise OSError("flake")

    with pytest.raises(OSError):
        rec.RetryPolicy(max_attempts=10).call(flaky, budget=budget)
    assert len(calls) == 2              # 1 try + 1 budgeted retry
    assert budget.spent == 1


def test_retry_delay_backoff_and_seeded_jitter():
    policy = rec.RetryPolicy(max_attempts=4, base_delay=0.1,
                             backoff_factor=2.0, max_delay=0.3)
    assert policy.delay(1) == pytest.approx(0.1)
    assert policy.delay(2) == pytest.approx(0.2)
    assert policy.delay(3) == pytest.approx(0.3)   # capped
    jittered = rec.RetryPolicy(max_attempts=4, base_delay=0.1,
                               jitter=0.5)
    import random
    a = jittered.delay(1, random.Random(3))
    b = jittered.delay(1, random.Random(3))
    assert a == b                       # deterministic for a seeded rng
    assert 0.05 <= a <= 0.15


def test_datastore_replica_exhaustion_is_permanent():
    store = ReplicatedDataStore(2, seed=0)
    store.put_all({0: np.zeros(4, dtype=np.float32)})
    for n in store.nodes:
        n.failing = True
    with pytest.raises(DataNodeError) as ei:
        store.fetch(0)
    assert rec.is_permanent(ei.value)


# ---------------------------------------------------------------------------
# CheckpointManager: async error surfacing
# ---------------------------------------------------------------------------


def test_checkpoint_background_error_surfaces_on_wait(tmp_path,
                                                      monkeypatch):
    from repro.checkpoint import manager as mgr_mod
    mgr = mgr_mod.CheckpointManager(str(tmp_path / "ck"))

    def boom(tree):
        raise OSError("disk full")

    monkeypatch.setattr(mgr_mod, "_flatten_with_names", boom)
    mgr.save(0, {"w": np.zeros(3, dtype=np.float32)})
    with pytest.raises(OSError, match="disk full"):
        mgr.wait()
    mgr.wait()                          # error raised once, then cleared


def test_checkpoint_background_error_surfaces_on_next_save(tmp_path,
                                                           monkeypatch):
    from repro.checkpoint import manager as mgr_mod
    mgr = mgr_mod.CheckpointManager(str(tmp_path / "ck"))
    state = {"w": np.zeros(3, dtype=np.float32)}

    def boom(tree):
        raise OSError("disk full")

    monkeypatch.setattr(mgr_mod, "_flatten_with_names", boom)
    mgr.save(0, state)
    with pytest.raises(OSError, match="disk full"):
        mgr.save(1, state)              # next save waits first: surfaces


def test_checkpoint_atomic_rename_keeps_last_good_step(tmp_path):
    from repro.checkpoint.manager import CheckpointManager
    d = str(tmp_path / "ck")
    mgr = CheckpointManager(d)
    mgr.save(0, {"w": np.arange(3, dtype=np.float32)}, blocking=True)
    # a crash mid-write leaves only a .tmp — never a visible step
    os.makedirs(os.path.join(d, "step_00000001.tmp"))
    assert mgr.all_steps() == [0]
    got = mgr.restore_latest()
    np.testing.assert_array_equal(got["['w']"], np.arange(3,
                                                          dtype=np.float32))


# ---------------------------------------------------------------------------
# Satellite regressions: fetch_many failover racing close(); pool
# worker death between claim and settlement
# ---------------------------------------------------------------------------


def test_fetch_many_mid_batch_failover_racing_close():
    store = ReplicatedDataStore(3, seed=0)
    samples = {i: np.full(16, i, dtype=np.float32) for i in range(8)}
    store.put_all(samples)
    store.nodes[1].failing = True       # mid-batch failures every round
    errors = []
    stop = threading.Event()

    def fetcher():
        while not stop.is_set():
            try:
                out = store.fetch_many(list(range(8)))
                for i, a in enumerate(out):
                    assert float(a[0]) == float(i)
            except Exception as e:      # noqa: BLE001
                errors.append(e)
                return

    threads = [threading.Thread(target=fetcher) for _ in range(3)]
    for th in threads:
        th.start()
    for _ in range(30):                 # close() races the in-flight pool
        store.close()
        time.sleep(0.002)
    stop.set()
    for th in threads:
        th.join(timeout=10)
        assert not th.is_alive()
    assert errors == []
    # inflight accounting settled: no claim leaked through the races
    assert all(n.inflight == 0 for n in store.nodes)
    store.close()


def test_service_pool_worker_death_between_claim_and_settlement():
    """A pool worker that dies after claiming (WorkerCrash from the
    crash hook — exactly the claim→settlement window) must not lose the
    job: the monitor respawns the thread and lease/crash reclamation
    requeues the claims, bit-identical to the fault-free run."""
    from repro.core import subsample as ss
    from repro.data.synthetic import NetflixSpec, netflix_dataset
    from repro.platform import PlatformSpec
    from repro.platform.service import PlatformService

    samples, months = netflix_dataset(
        NetflixSpec(n_movies=12, mean_ratings=512))
    spec = PlatformSpec(platform="BTS", n_workers=2, backend="threaded",
                        knee_bytes=4 * 1024 * 4, seed=5,
                        lease_seconds=0.5)

    def run(injector=None):
        svc = PlatformService(spec, fault_injector=injector)
        with svc:
            h = svc.register_dataset(samples, months)
            t = svc.submit(h, ss.NETFLIX_HIGH)
            r = t.result(timeout=120)
        return r, svc

    clean, _ = run()
    inj = FaultInjector(FaultPlan(events=[
        FaultEvent(kind="worker_crash", target=0, at_claims=1)]))
    faulty, svc = run(injector=inj)
    assert [e.kind for e in inj.fired] == ["worker_crash"]
    assert svc._pool.worker_respawns == 1
    for k in clean:
        np.testing.assert_array_equal(np.asarray(clean[k]),
                                      np.asarray(faulty[k]))


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_service_pool_monitor_respawns_hard_thread_death():
    """A worker thread that dies WITHOUT self-reporting (an unexpected
    exception, not WorkerCrash) is detected by the supervision monitor,
    its claims reclaimed via on_worker_dead, and the thread respawned."""
    from repro.core import subsample as ss
    from repro.data.synthetic import NetflixSpec, netflix_dataset
    from repro.platform import PlatformSpec
    from repro.platform.service import PlatformService

    samples, months = netflix_dataset(
        NetflixSpec(n_movies=12, mean_ratings=512))
    spec = PlatformSpec(platform="BTS", n_workers=2, backend="threaded",
                        knee_bytes=4 * 1024 * 4, seed=5,
                        lease_seconds=0.5)
    died = threading.Event()

    def hard_death(wid):
        if wid == 0 and not died.is_set():
            died.set()
            raise RuntimeError("segfault stand-in: thread dies silently")

    with PlatformService(spec) as ref_svc:
        h = ref_svc.register_dataset(samples, months)
        clean = ref_svc.submit(h, ss.NETFLIX_HIGH).result(timeout=120)

    class HookInjector:
        """Minimal injector stand-in: only the crash hook matters."""

        def __init__(self):
            self.fired = []

        def worker_tick(self, wid):
            hard_death(wid)

        def wrap_emit(self, emit):
            return emit

        def attach_store(self, store):
            pass

    svc = PlatformService(spec, fault_injector=HookInjector())
    with svc:
        h = svc.register_dataset(samples, months)
        t = svc.submit(h, ss.NETFLIX_HIGH)
        r = t.result(timeout=120)
    assert died.is_set()
    assert svc._pool.worker_respawns == 1
    for k in clean:
        np.testing.assert_array_equal(np.asarray(clean[k]),
                                      np.asarray(r[k]))
