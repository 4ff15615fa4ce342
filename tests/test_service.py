"""Multi-tenant service layer (ISSUE 3): submit/Platform.run bit
identity on both backends, dataset-registry arena caching, cross-job
wave fusion, DRR fairness + deadline boost, SLO-aware admission,
cancellation, reduce-tree failure paths, and the concurrent datastore
fetch path."""

import threading
import time

import numpy as np
import pytest

from repro.core import scheduler as sch
from repro.core import subsample as ss
from repro.core.datastore import DataNode, ReplicatedDataStore
from repro.data.synthetic import (
    EagletSpec,
    NetflixSpec,
    eaglet_dataset,
    netflix_dataset,
)
from repro.platform import (
    AdmissionError,
    AdmissionPolicy,
    CancelledError,
    MomentsSpec,
    Platform,
    PlatformService,
    PlatformSpec,
    PoolJob,
    ServicePool,
    StreamingReduceTree,
    resolve_platform_config,
)

WL = MomentsSpec(draws=4, draw_size=16)
KNEE = 4 * 96 * 4


def _dataset(n, length=96, seed=0):
    rng = np.random.default_rng(seed)
    samples = {i: rng.standard_normal(length).astype(np.float32)
               for i in range(n)}
    months = {i: np.zeros(length, np.int32) for i in range(n)}
    return samples, months


def _spec(**kw):
    base = dict(platform="BTS", n_workers=2, backend="threaded",
                knee_bytes=KNEE, seed=0, max_wave=16)
    base.update(kw)
    return PlatformSpec(**base)


# -- submit ≡ Platform.run (acceptance criterion) -----------------------------


@pytest.fixture(scope="module")
def netflix():
    return netflix_dataset(NetflixSpec(n_movies=24, mean_ratings=1024))


@pytest.mark.parametrize("backend", ["threaded", "simulated"])
@pytest.mark.parametrize("workload", [ss.NETFLIX_HIGH, ss.NETFLIX_LOW],
                         ids=["netflix_high", "netflix_low"])
def test_submit_bit_identical_to_platform_run_netflix(netflix, workload,
                                                      backend):
    samples, months = netflix
    spec = _spec(backend=backend, n_workers=3, knee_bytes=4 * 1024 * 4,
                 seed=11)
    base = Platform(spec).run(samples, months, workload)
    with PlatformService(spec) as svc:
        handle = svc.register_dataset(samples, months)
        got = svc.submit(handle, workload, seed=11).result(timeout=300)
    for key in base.result:
        np.testing.assert_array_equal(
            np.asarray(base.result[key]), np.asarray(got[key]),
            err_msg=f"{workload.name}/{backend} diverged on {key!r}")


@pytest.mark.parametrize("backend", ["threaded", "simulated"])
def test_submit_bit_identical_to_platform_run_eaglet(backend):
    samples, months = eaglet_dataset(EagletSpec(n_families=24,
                                                mean_markers=512))
    spec = _spec(backend=backend, knee_bytes=8 * 512 * 4, seed=3)
    base = Platform(spec).run(samples, months, ss.EAGLET)
    with PlatformService(spec) as svc:
        handle = svc.register_dataset(samples, months)
        got = svc.submit(handle, ss.EAGLET, seed=3).result(timeout=300)
    np.testing.assert_array_equal(base.result["alod"], got["alod"])


def test_submit_bit_identical_moments_wave_class(netflix):
    """The wave-fused service path agrees with the standalone wave
    driver on the kernel-backed moments statistic."""
    samples, months = _dataset(32)
    spec = _spec(seed=7)
    base = Platform(spec).run(samples, months, WL)
    with PlatformService(spec) as svc:
        handle = svc.register_dataset(samples, months)
        got = svc.submit(handle, WL, seed=7).result(timeout=120)
    for key in base.result:
        np.testing.assert_array_equal(
            np.asarray(base.result[key]), np.asarray(got[key]),
            err_msg=f"service wave diverged on {key!r}")


# -- registry / arena caching -------------------------------------------------


def test_repeat_queries_hit_cached_arena():
    samples, months = _dataset(32)
    with PlatformService(_spec()) as svc:
        handle = svc.register_dataset(samples, months, name="cached")
        first = svc.submit(handle, WL, seed=1)
        first.result(timeout=120)
        repeats = [svc.submit(handle, WL, seed=s) for s in (2, 3, 4)]
        for t in repeats:
            t.result(timeout=120)
    assert first.bytes_uploaded > 10_000      # paid the arena pack
    for t in repeats:                         # slot/seed vectors only
        assert t.bytes_uploaded < 0.01 * first.bytes_uploaded
    # repeat queries skip plan+pack: they must be much faster
    assert min(t.latency for t in repeats) < first.latency


def test_query_classes_are_isolated_per_workload():
    samples, months = _dataset(24)
    other = MomentsSpec(draws=2, draw_size=16)     # 32 draws vs WL's 64
    with PlatformService(_spec()) as svc:
        handle = svc.register_dataset(samples, months)
        a = svc.submit(handle, WL, seed=0)
        b = svc.submit(handle, other, seed=0)
        ra, rb = a.result(timeout=120), b.result(timeout=120)
        n_classes = len(handle._classes)
    assert n_classes == 2                     # one arena per query class
    assert ra["count"] == 2.0 * rb["count"]


# -- cross-job wave fusion ----------------------------------------------------


def test_concurrent_jobs_fuse_waves_across_jobs():
    # 10 tasks/job with wave width 8 leaves a 2-task tail per job — the
    # fusion fill packs peer jobs' tasks into those tails
    samples, months = _dataset(40)
    with PlatformService(_spec()) as svc:
        handle = svc.register_dataset(samples, months)
        svc.submit(handle, WL, seed=99).result(timeout=120)  # build class
        tickets = [svc.submit(handle, WL, seed=i) for i in range(8)]
        for t in tickets:
            t.result(timeout=120)
        stats = svc.stats()
    assert stats["fused_dispatches"] > 0
    # one shape bucket: no claim ever reaches past a mismatch
    assert stats["tasks_pulled_forward"] == 0
    # 8 jobs x 10 tasks in far fewer dispatches than tasks
    post_warm = [w for w in stats["wave_sizes"]]
    assert sum(post_warm) == 90
    assert stats["device_dispatches"] < 40


def test_fused_results_match_sequential_results():
    samples, months = _dataset(40)
    spec = _spec()
    seq = {s: Platform(spec_s).run(samples, months, WL).result
           for s, spec_s in ((s, PlatformSpec(
               **{**spec.__dict__, "seed": s})) for s in range(4))}
    with PlatformService(spec) as svc:
        handle = svc.register_dataset(samples, months)
        tickets = {s: svc.submit(handle, WL, seed=s) for s in range(4)}
        for s, t in tickets.items():
            got = t.result(timeout=120)
            for key in seq[s]:
                np.testing.assert_array_equal(
                    np.asarray(seq[s][key]), np.asarray(got[key]),
                    err_msg=f"seed {s} diverged on {key!r}")


@pytest.mark.parametrize(
    "queues, cap, settled, claimed, left, pulled", [
        # alternating buckets: four As, the Bs keep their order
        ({1: "ABABABABAB"}, 4, (), [0, 2, 4, 6],
         {1: [1, 3, 5, 7, 8, 9]}, 3),
        # cap 1: the head only
        ({1: "ABABABABAB"}, 1, (), [0], {1: list(range(1, 10))}, 0),
        # one bucket: the head run, nothing pulled forward
        ({1: "AAAAAA"}, 4, (), [0, 1, 2, 3], {1: [4, 5]}, 0),
        # a settled lease-reclaimed duplicate met in the scan is dropped
        ({1: "ABABABABAB"}, 4, (2,), [0, 4, 6, 8], {1: [1, 3, 5, 7, 9]}, 3),
        # the peer fill reaches behind the peer's head
        ({1: "AA", 2: "BABAAB"}, 4, (), [0, 1, 101, 103],
         {1: [], 2: [100, 102, 104, 105]}, 2),
    ], ids=["alternating", "cap1", "one-bucket", "duplicate", "peer"])
def test_claim_fills_the_wave_from_the_whole_queue(queues, cap, settled,
                                                   claimed, left, pulled):
    msched = sch.MultiJobScheduler(1)
    for jid, keys in queues.items():
        msched.add_job(jid, [sch.Task(100 * (jid - 1) + i, (i,), 1.0,
                                      payload=k)
                             for i, k in enumerate(keys)],
                       fuse_key=lambda t: t.payload, cap=cap)
    msched.jobs[1].completed_ids.update(settled)
    batch = msched.claim(now=0.0)
    assert [t.task_id for _, t in batch] == claimed
    assert {jid: [t.task_id for t in job.pending]
            for jid, job in msched.jobs.items()} == left
    assert msched.tasks_pulled_forward == pulled


# -- fairness / deadlines -----------------------------------------------------


def test_drr_small_job_not_starved_by_big_job():
    # the big job must outlast the second submit's latency on a loaded
    # host, or it finishes before the small job even arrives
    samples, months = _dataset(1024)
    small_samples, _ = _dataset(32)
    with PlatformService(_spec(n_workers=1)) as svc:
        big_h = svc.register_dataset(samples, months)
        small_h = svc.register_dataset(small_samples,
                                       {i: np.zeros(96, np.int32)
                                        for i in range(32)})
        # warm both classes so the measured run is execution only
        svc.submit(big_h, WL, seed=90).result(timeout=300)
        svc.submit(small_h, WL, seed=91).result(timeout=300)
        big = svc.submit(big_h, WL, seed=1)       # 256 tasks
        small = svc.submit(small_h, WL, seed=2)   # 8 tasks
        small.result(timeout=300)
        big.result(timeout=300)
    assert small.finished_at < big.finished_at


def test_deadline_boost_prefers_urgent_job():
    cfg = sch.MultiJobConfig(quantum=4.0)
    msched = sch.MultiJobScheduler(1, cfg)
    mk = lambda n, base: [sch.Task(base + i, (i,), 1.0) for i in range(n)]
    msched.avg_task_seconds = 0.1
    msched.add_job(1, mk(50, 0), fuse_key=lambda t: "a", cap=4)
    msched.add_job(2, mk(4, 100), fuse_key=lambda t: "a", cap=4,
                   deadline=1.0)   # 4 tasks x 0.1s: needs the pool NOW
    batch = msched.claim(now=0.75)
    assert {j.job_id for j, _ in batch} == {2}


def test_multijob_scheduler_drr_alternates_jobs():
    msched = sch.MultiJobScheduler(1, sch.MultiJobConfig(quantum=2.0))
    msched.add_job(1, [sch.Task(i, (i,), 1.0) for i in range(8)],
                   fuse_key=lambda t: ("j1",), cap=2)
    msched.add_job(2, [sch.Task(100 + i, (i,), 1.0) for i in range(8)],
                   fuse_key=lambda t: ("j2",), cap=2)
    order = []
    while True:
        batch = msched.claim(now=0.0)
        if not batch:
            break
        order.append(batch[0][0].job_id)
        for job, _t in batch:
            msched.on_task_complete(job.job_id, 1e-3)
    assert order == [1, 2, 1, 2, 1, 2, 1, 2]


def test_multijob_fusion_charges_peer_deficit():
    msched = sch.MultiJobScheduler(1, sch.MultiJobConfig(quantum=8.0))
    key = lambda t: ("shared",)
    msched.add_job(1, [sch.Task(i, (i,), 1.0) for i in range(2)],
                   fuse_key=key, cap=8)
    msched.add_job(2, [sch.Task(100 + i, (i,), 1.0) for i in range(8)],
                   fuse_key=key, cap=8)
    batch = msched.claim(now=0.0)
    # job 1's 2 tasks + 6 fused from job 2, in one claim
    assert [j.job_id for j, _ in batch] == [1, 1, 2, 2, 2, 2, 2, 2]
    assert msched.fused_dispatches == 1
    assert msched.jobs[2].deficit < 0          # fused service was charged


def test_priority_tier_served_first():
    msched = sch.MultiJobScheduler(1)
    msched.add_job(1, [sch.Task(i, (i,), 1.0) for i in range(4)],
                   fuse_key=lambda t: ("lo",), cap=4, priority=0)
    msched.add_job(2, [sch.Task(100 + i, (i,), 1.0) for i in range(4)],
                   fuse_key=lambda t: ("hi",), cap=4, priority=5)
    batch = msched.claim(now=0.0)
    assert {j.job_id for j, _ in batch} == {2}


def test_cancel_mid_rotation_job_does_not_break_claim():
    # cancelling a queued non-front job used to leave its id in the
    # round-robin rotation, so the next claim raised KeyError and killed
    # the pool worker thread
    msched = sch.MultiJobScheduler(1, sch.MultiJobConfig(quantum=2.0))
    for jid in (1, 2, 3):
        msched.add_job(jid, [sch.Task(100 * jid + i, (i,), 1.0)
                             for i in range(4)],
                       fuse_key=lambda t, _j=jid: (_j,), cap=2)
    assert msched.cancel_job(2)            # queued, never claimed
    served = set()
    while True:
        batch = msched.claim(now=0.0)      # must not raise
        if not batch:
            break
        for job, _t in batch:
            served.add(job.job_id)
            msched.on_task_complete(job.job_id, 1e-3)
    assert served == {1, 3}


def test_fail_job_with_pending_tasks_does_not_break_claim():
    # a batch failure in a job that still has pending tasks removes the
    # job; the rotation must forget it too
    msched = sch.MultiJobScheduler(1, sch.MultiJobConfig(quantum=2.0))
    msched.add_job(1, [sch.Task(i, (i,), 1.0) for i in range(8)],
                   fuse_key=lambda t: ("j1",), cap=2)
    msched.add_job(2, [sch.Task(100 + i, (i,), 1.0) for i in range(8)],
                   fuse_key=lambda t: ("j2",), cap=2)
    batch = msched.claim(now=0.0)
    assert {j.job_id for j, _ in batch} == {1} and msched.jobs[1].pending
    msched.fail_job(1)
    batch = msched.claim(now=0.0)          # must not raise
    assert {j.job_id for j, _ in batch} == {2}


def test_cancelled_settlement_does_not_skew_task_ema():
    msched = sch.MultiJobScheduler(1)
    msched.add_job(1, [sch.Task(i, (i,), 1.0) for i in range(2)],
                   fuse_key=lambda t: ("j1",), cap=2)
    msched.claim(now=0.0)
    msched.avg_task_seconds = 0.5
    # tasks claimed from a since-cancelled job settle without a sample
    assert not msched.on_task_complete(1, None)
    assert msched.on_task_complete(1, None)
    assert msched.avg_task_seconds == 0.5
    assert 1 not in msched.jobs


# -- admission control --------------------------------------------------------


def test_admission_shed_rejects_over_capacity():
    samples, months = _dataset(64)
    policy = AdmissionPolicy(max_active_jobs=1, mode="shed")
    with PlatformService(_spec(n_workers=1), admission=policy) as svc:
        handle = svc.register_dataset(samples, months)
        first = svc.submit(handle, WL, seed=0)
        shed = svc.submit(handle, WL, seed=1)
        first.result(timeout=120)
    assert shed.status == "rejected"
    with pytest.raises(AdmissionError):
        shed.result(timeout=5)


def test_concurrent_first_submits_share_one_pool():
    # unsynchronized lazy pool creation used to let two racing first
    # submits each build + start a resident pool (orphaning one)
    samples, months = _dataset(48)
    with PlatformService(_spec()) as svc:
        handle = svc.register_dataset(samples, months)
        tickets, errs = [], []

        def go(s):
            try:
                tickets.append(svc.submit(handle, WL, seed=s))
            except BaseException as e:      # noqa: BLE001
                errs.append(e)

        threads = [threading.Thread(target=go, args=(s,)) for s in range(8)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert not errs
        for t in tickets:
            t.result(timeout=120)
        assert len(svc._pool._threads) == svc.spec.n_workers


def test_submit_racing_close_never_strands_a_ticket():
    # a submit that passed the _closed check while close() ran used to
    # hand its job to a stopped pool, hanging result() forever
    samples, months = _dataset(48)
    for _trial in range(3):
        svc = PlatformService(_spec())
        handle = svc.register_dataset(samples, months)
        got = []

        def racer(s):
            try:
                got.append(svc.submit(handle, WL, seed=s))
            except RuntimeError:
                pass                        # "service is closed"

        threads = [threading.Thread(target=racer, args=(s,))
                   for s in range(6)]
        for th in threads:
            th.start()
        svc.close()
        for th in threads:
            th.join()
        for ticket in got:
            try:
                ticket.result(timeout=30)   # must resolve, not hang
            except TimeoutError:
                pytest.fail(f"ticket {ticket.job_id} stranded "
                            f"(status={ticket.status})")
            except BaseException:           # noqa: BLE001
                pass                        # rejected/failed/closed: fine


def test_admission_queue_admits_when_capacity_frees():
    samples, months = _dataset(64)
    policy = AdmissionPolicy(max_active_jobs=1, mode="queue")
    with PlatformService(_spec(n_workers=1), admission=policy) as svc:
        handle = svc.register_dataset(samples, months)
        first = svc.submit(handle, WL, seed=0)
        queued = svc.submit(handle, WL, seed=1)
        assert queued.status == "queued"
        r1 = first.result(timeout=120)
        r2 = queued.result(timeout=120)
    assert r1["count"] == r2["count"]
    assert queued.queue_wait is not None and queued.queue_wait >= 0


def test_admission_idle_pool_admits_job_larger_than_queue_bound():
    # a job with more tasks than max_pending_tasks used to wait for a
    # drain that could never make room, so result() hung forever
    samples, months = _dataset(32)
    policy = AdmissionPolicy(max_pending_tasks=4, mode="queue")
    with PlatformService(_spec(n_workers=1), admission=policy) as svc:
        handle = svc.register_dataset(samples, months)
        big = svc.submit(handle, WL, seed=0)
        assert big.n_tasks > policy.max_pending_tasks
        assert big.result(timeout=120)["count"] == big.n_tasks * 64


def test_admission_shed_admits_oversized_job_only_into_idle_service():
    samples, months = _dataset(64)
    policy = AdmissionPolicy(max_pending_tasks=4, mode="shed")
    with PlatformService(_spec(n_workers=1), admission=policy) as svc:
        handle = svc.register_dataset(samples, months)
        first = svc.submit(handle, WL, seed=0)     # idle service: admitted
        shed = svc.submit(handle, WL, seed=1)      # first still running
        assert first.n_tasks > policy.max_pending_tasks
        first.result(timeout=120)
    assert shed.status == "rejected"
    with pytest.raises(AdmissionError):
        shed.result(timeout=5)


def test_admission_queue_runs_oversized_jobs_one_at_a_time():
    # each oversized job waits for the service to go idle; the FIFO
    # drain must then admit the head even while others wait behind it
    samples, months = _dataset(64)
    policy = AdmissionPolicy(max_pending_tasks=4, mode="queue")
    with PlatformService(_spec(n_workers=1), admission=policy) as svc:
        handle = svc.register_dataset(samples, months)
        tickets = [svc.submit(handle, WL, seed=s) for s in range(3)]
        assert [t.status for t in tickets[1:]] == ["queued", "queued"]
        counts = [t.result(timeout=120)["count"] for t in tickets]
    assert counts == [tickets[0].n_tasks * 64] * 3


def test_slo_aware_admission_rejects_unmeetable_deadline():
    samples, months = _dataset(32)
    with PlatformService(_spec(n_workers=1)) as svc:
        handle = svc.register_dataset(samples, months)
        svc.submit(handle, WL, seed=0).result(timeout=120)  # seeds the EMA
        doomed = svc.submit(handle, WL, seed=1, deadline=1e-9)
    assert doomed.status == "rejected"
    assert "slo" in doomed.reason
    with pytest.raises(AdmissionError):
        doomed.result(timeout=5)


# -- streaming / cancellation -------------------------------------------------


def test_partial_estimates_stream_while_running():
    samples, months = _dataset(256)
    with PlatformService(_spec(n_workers=1)) as svc:
        handle = svc.register_dataset(samples, months)
        svc.submit(handle, WL, seed=9).result(timeout=300)   # warm class
        ticket = svc.submit(handle, WL, seed=1)
        saw_partial = False
        for _ in range(2000):
            p = ticket.partial()
            done, total = ticket.progress()
            if p is not None and done < total:
                saw_partial = True
                # new snapshot shape (DESIGN.md §10): CI fields + the
                # finalized running statistic under "estimate"
                assert {"value", "ci_low", "ci_high", "tasks_in",
                        "estimate"} <= set(p)
                assert set(p["estimate"]) == {"mean", "var", "count"}
                break
            if ticket.status == "done":
                break
            time.sleep(1e-3)
        final = ticket.result(timeout=300)
    assert saw_partial or final is not None   # tiny jobs may finish first
    assert set(final) == {"mean", "var", "count"}


def test_cancel_running_job():
    samples, months = _dataset(256)
    with PlatformService(_spec(n_workers=1)) as svc:
        handle = svc.register_dataset(samples, months)
        svc.submit(handle, WL, seed=9).result(timeout=300)
        victim = svc.submit(handle, WL, seed=1)
        bystander = svc.submit(handle, WL, seed=2)
        assert svc.cancel(victim)
        with pytest.raises(CancelledError):
            victim.result(timeout=30)
        bystander.result(timeout=300)          # peers unaffected
    assert victim.status == "cancelled"
    assert bystander.status == "done"


def test_close_unblocks_outstanding_jobs():
    """close() must not leave a running job's result() hanging forever:
    the ticket either finished normally or fails with a service-closed
    error — never a silent deadlock."""
    samples, months = _dataset(256)
    svc = PlatformService(_spec(n_workers=1))
    handle = svc.register_dataset(samples, months)
    svc.submit(handle, WL, seed=9).result(timeout=300)    # warm the class
    ticket = svc.submit(handle, WL, seed=1)               # 64 tasks
    svc.close()
    try:
        ticket.result(timeout=30)
        assert ticket.status == "done"
    except RuntimeError as e:
        assert ticket.status == "failed"
        assert "closed" in str(e)


def test_pool_batch_failure_isolates_other_jobs():
    plat = resolve_platform_config(_spec())
    pool = ServicePool(1, plat)
    done, failed = threading.Event(), threading.Event()
    errors = []

    def boom(items):
        raise RuntimeError("injected batch failure")

    def ok(items):
        return [{"count": np.float32(1.0)} for _ in items]

    tasks_a = [sch.Task(i, (i,), 1.0) for i in range(4)]
    tasks_b = [sch.Task(i, (i,), 1.0) for i in range(4)]
    pool.submit(PoolJob(
        job_id=1, tasks=tasks_a, seed=0, run_batch=boom,
        emit=lambda tid, v: None, on_done=lambda: None,
        on_error=lambda e: (errors.append(e), failed.set()),
        fuse_key=lambda t: ("a",), cap=4))
    pool.submit(PoolJob(
        job_id=2, tasks=tasks_b, seed=0, run_batch=ok,
        emit=lambda tid, v: None, on_done=done.set,
        on_error=lambda e: None,
        fuse_key=lambda t: ("b",), cap=4))
    assert failed.wait(30), "failing job never reported its error"
    assert done.wait(30), "healthy job blocked by peer's failure"
    pool.close()
    assert isinstance(errors[0], RuntimeError)


# -- reduce tree failure paths (satellite) ------------------------------------


def test_reduce_combine_exception_propagates_to_result():
    def bad_combine(a, b):
        raise ValueError("combine blew up")

    tree = StreamingReduceTree(4, combine=bad_combine)
    for i in range(4):
        tree.offer(i, {"x": np.float32(i)})
    with pytest.raises(ValueError, match="combine blew up"):
        tree.result(timeout=30)


def test_reduce_result_times_out_instead_of_deadlocking():
    tree = StreamingReduceTree(3)
    tree.offer(0, {"x": np.float32(1)})       # leaves 1, 2 never arrive
    t0 = time.perf_counter()
    with pytest.raises(TimeoutError):
        tree.result(timeout=0.2)
    assert time.perf_counter() - t0 < 5.0
    tree.close()                               # cancellation path unblocks


def test_reduce_snapshot_is_nondestructive():
    tree = StreamingReduceTree(4)
    tree.offer(0, {"x": np.float32(1)})
    tree.offer(1, {"x": np.float32(2)})
    for _ in range(200):
        snap = tree.snapshot()
        if snap is not None and float(snap["x"]) == 3.0:
            break
        time.sleep(1e-3)
    assert float(tree.snapshot()["x"]) == 3.0
    tree.offer(2, {"x": np.float32(3)})
    tree.offer(3, {"x": np.float32(4)})
    assert float(tree.result(timeout=30)["x"]) == 10.0


# -- datastore satellites -----------------------------------------------------


def test_fetch_many_spreads_batch_across_replicas():
    store = ReplicatedDataStore(n_initial=3)
    data = {i: np.full(8, i, np.float32) for i in range(12)}
    store.put_all(data)
    seen = []
    for node in store.nodes:
        orig = node.fetch

        def spy(sample_id, inflight=None, _orig=orig, _nid=node.node_id):
            seen.append(_nid)
            return _orig(sample_id, inflight)

        node.fetch = spy
    out = store.fetch_many(list(range(12)))
    for i, arr in enumerate(out):              # order preserved
        np.testing.assert_array_equal(arr, data[i])
    assert len(set(seen)) == 3, "batch did not spread across replicas"


def test_fetch_many_concurrent_observations_recorded():
    store = ReplicatedDataStore(n_initial=2,
                                latency=lambda nbytes: 1e-4)
    store.put_all({i: np.zeros(16, np.float32) for i in range(8)})
    store.fetch_many(list(range(8)))
    assert len(store._obs) == 8


def test_datanode_latency_uses_inflight_snapshot():
    # base latency well above scheduler jitter so the modelled-contention
    # ratio cannot be flipped by wall-clock noise on a busy runner
    node = DataNode(0, latency=lambda nbytes: 1e-2)
    node.store[0] = np.zeros(1024, np.float32)
    node.inflight = 40                         # racing counter, ignored
    _, calm = node.fetch(0, inflight=1)
    _, contended = node.fetch(0, inflight=16)
    # 16 inflight vs parallelism 4 ⇒ 4x modelled queueing vs calm
    assert contended > calm * 2.5              # model saw the snapshot
