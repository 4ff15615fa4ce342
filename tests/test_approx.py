"""Error-bounded approximate query engine (ISSUE 5, DESIGN.md §10):
estimator math, reduce-tree streaming estimates under concurrency and
mid-job cancellation, scheduler cancel plumbing, and early termination
end-to-end through the driver and the service."""

import math
import threading
import time
import tracemalloc

import numpy as np
import pytest

from repro.core.estimator import (
    _BLOCK,
    EXTRACTORS,
    EstimateSnapshot,
    ReplayStopper,
    StoppingController,
    SubsampleEstimator,
    normal_ppf,
    z_for_confidence,
)
from repro.core.scheduler import SchedulerConfig, Task, TwoPhaseScheduler
from repro.platform import (
    MomentsSpec,
    PartialEstimate,
    Platform,
    PlatformService,
    PlatformSpec,
    StreamingReduceTree,
)

WL = MomentsSpec(draws=4, draw_size=16)
SAMPLE_LEN = 64
KNEE = 2 * SAMPLE_LEN * 4              # 2 samples/task


def _dataset(n, seed=0):
    rng = np.random.default_rng(seed)
    samples = {i: rng.standard_normal(SAMPLE_LEN).astype(np.float32)
               for i in range(n)}
    months = {i: np.zeros(SAMPLE_LEN, np.int32) for i in range(n)}
    return samples, months


def _spec(**kw):
    base = dict(platform="BTS", n_workers=2, backend="threaded",
                knee_bytes=KNEE, seed=0, max_wave=8)
    base.update(kw)
    return PlatformSpec(**base)


def _moments_partial(rng, d=8, count=100.0):
    v = rng.normal(3.0, 0.1, d)
    return {"sum": v * count, "sumsq": v * v * count,
            "count": np.asarray(count, np.float32)}


# -- estimator math ----------------------------------------------------------


def test_normal_ppf_matches_known_quantiles():
    assert normal_ppf(0.5) == pytest.approx(0.0, abs=1e-9)
    assert normal_ppf(0.975) == pytest.approx(1.959964, abs=1e-5)
    assert normal_ppf(0.025) == pytest.approx(-1.959964, abs=1e-5)
    assert z_for_confidence(0.99) == pytest.approx(2.575829, abs=1e-5)
    with pytest.raises(ValueError):
        normal_ppf(0.0)


def test_estimator_deterministic_under_completion_order():
    rng = np.random.default_rng(0)
    partials = {tid: _moments_partial(rng) for tid in range(24)}
    a = SubsampleEstimator("moments")
    b = SubsampleEstimator("moments")
    for tid in range(24):
        a.observe(tid, partials[tid])
    for tid in reversed(range(24)):
        b.observe(tid, partials[tid])
    sa, sb = a.estimate(), b.estimate()
    assert np.array_equal(sa.value, sb.value)
    assert np.array_equal(sa.ci_low, sb.ci_low)
    assert np.array_equal(sa.ci_high, sb.ci_high)
    assert sa.half_width == sb.half_width


def test_estimator_ci_shrinks_with_tasks():
    rng = np.random.default_rng(1)
    est = SubsampleEstimator("moments")
    widths = []
    for tid in range(64):
        est.observe(tid, _moments_partial(rng))
        if tid + 1 in (4, 16, 64):
            widths.append(est.estimate().half_width)
    assert widths[0] > widths[1] > widths[2]
    # roughly the 1/sqrt(k) CLT law (x4 tasks => ~x2 narrower)
    assert widths[0] / widths[2] > 2.0


def test_estimator_single_task_has_no_interval():
    est = SubsampleEstimator("moments")
    est.observe(0, _moments_partial(np.random.default_rng(0)))
    snap = est.estimate()
    assert snap.tasks_in == 1
    assert np.isinf(snap.half_width)


def test_estimator_unsupported_statistic_is_conservative():
    est = SubsampleEstimator("custom")
    assert not est.supported
    est.observe(0, {"anything": np.ones(3)})
    assert est.estimate() is None
    ctl = StoppingController(est, epsilon=1e9, min_tasks=2)
    assert not ctl.should_stop()           # never converges, never stops


def test_estimator_masks_unsupported_components():
    # month 0 never drawn by task 1: that component carries no interval,
    # the band is computed over the supported components only
    est = SubsampleEstimator("monthly_mean")
    est.observe(0, {"sum": np.array([4.0, 8.0]),
                    "count": np.array([2.0, 2.0])})
    est.observe(1, {"sum": np.array([0.0, 6.0]),
                    "count": np.array([0.0, 2.0])})
    snap = est.estimate()
    assert np.isnan(snap.ci_low[0]) and np.isnan(snap.ci_high[0])
    assert np.isfinite(snap.half_width)
    assert snap.contains(np.array([123.0, 3.5]))   # NaN comp is skipped


def test_simultaneous_band_widens_with_dimensionality():
    rng = np.random.default_rng(2)
    one, many = SubsampleEstimator("moments"), SubsampleEstimator("moments")
    for tid in range(16):
        p = _moments_partial(rng, d=64)
        many.observe(tid, p)
        one.observe(tid, {"sum": p["sum"][:1], "sumsq": p["sumsq"][:1],
                          "count": p["count"]})
    # Bonferroni: per-component z grows with D, so the 64-D band's
    # component-0 interval is strictly wider than the scalar interval
    w1 = one.estimate().ci_high[0] - one.estimate().ci_low[0]
    w64 = many.estimate().ci_high[0] - many.estimate().ci_low[0]
    assert w64 > w1 * 1.3


def _estimate_full_width(thetas, confidence):
    """The snapshot through the stacked [k, D] array and full-width
    temporaries: the formula the column-block estimate() must match."""
    stack = np.stack(thetas)
    k = stack.shape[0]
    value = stack.mean(axis=0)
    if k < 2:
        half = np.full_like(value, np.inf)
    else:
        sd = stack.std(axis=0, ddof=1)
        d = int(np.count_nonzero(~np.isnan(sd)))
        z = (normal_ppf(1.0 - (1.0 - confidence) / (2.0 * d))
             if d else z_for_confidence(confidence))
        half = z * sd / math.sqrt(k)
    valid = ~np.isnan(half)
    width = float(np.max(half[valid])) if valid.any() else math.inf
    return value, value - half, value + half, width


def _stat_partial(statistic, rng, d):
    """One task's partial with D = d components; ``monthly_mean`` and
    ``alod`` leave some components undrawn (months no task drew, cells
    no draw hit), which the snapshot carries as NaN."""
    if statistic == "moments":
        return _moments_partial(rng, d=d)
    counts = rng.integers(0, 4, d).astype(np.float64)
    counts[::5] = 0.0                       # never drawn by any task
    sums = rng.normal(3.0, 1.0, d) * counts
    if statistic == "monthly_mean":
        return {"sum": sums, "count": counts}
    return {"sum_curve": np.abs(sums), "hits": counts}


@pytest.mark.parametrize("d", [1, 64, 120, _BLOCK - 1, _BLOCK, _BLOCK + 1,
                               3 * _BLOCK + 5])
@pytest.mark.parametrize("k", [1, 2, 3, 7, 9])
@pytest.mark.parametrize("statistic", ["moments", "monthly_mean", "alod"])
def test_estimate_bit_identical_to_full_width(statistic, k, d):
    rng = np.random.default_rng(1000 * k + d)
    est = SubsampleEstimator(statistic, confidence=0.9)
    partials = {tid: _stat_partial(statistic, rng, d)
                for tid in rng.permutation(3 * k)[:k].tolist()}
    for tid, partial in partials.items():
        est.observe(tid, partial)
    thetas = [EXTRACTORS[statistic](partials[t]).reshape(-1)
              for t in sorted(partials)]
    value, ci_low, ci_high, width = _estimate_full_width(thetas, 0.9)
    snap = est.estimate()
    for got, want in ((snap.value, value), (snap.ci_low, ci_low),
                      (snap.ci_high, ci_high)):
        assert got.dtype == want.dtype and got.shape == want.shape
        assert np.array_equal(got, want, equal_nan=True)
    assert snap.half_width == width
    assert snap.tasks_in == k and snap.confidence == 0.9


def test_estimate_does_not_stack_the_task_estimates():
    # the outputs (three D-vectors) are allowed; a [k, D] float64 copy
    # of the per-task estimates, or full-width temporaries, are not
    k, d = 7, 1 << 20
    rng = np.random.default_rng(12)
    est = SubsampleEstimator("moments")
    for tid in range(k):
        est.observe(tid, {"sum": rng.standard_normal(d),
                          "count": np.asarray(1.0, np.float32)})
    tracemalloc.start()
    try:
        snap = est.estimate()
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert snap.tasks_in == k and np.isfinite(snap.half_width)
    assert peak < k * d * np.dtype(np.float64).itemsize


def test_stopping_controller_latches_and_reports():
    rng = np.random.default_rng(3)
    est = SubsampleEstimator("moments")
    ctl = StoppingController(est, epsilon=0.5, min_tasks=8)
    for tid in range(7):
        est.observe(tid, _moments_partial(rng))
        assert not ctl.should_stop()       # min_tasks floor
    for tid in range(7, 32):
        est.observe(tid, _moments_partial(rng))
    assert ctl.should_stop()
    assert ctl.stopped and "converged" in ctl.stop_reason
    assert isinstance(ctl.final, EstimateSnapshot)
    latched = ctl.final
    est.observe(99, _moments_partial(rng))
    assert ctl.should_stop() and ctl.final is latched


def test_stopping_controller_epsilon_none_never_stops():
    rng = np.random.default_rng(4)
    est = SubsampleEstimator("moments")
    ctl = StoppingController(est, epsilon=None, min_tasks=2)
    for tid in range(64):
        est.observe(tid, _moments_partial(rng))
    assert not ctl.should_stop()
    with pytest.raises(ValueError):
        StoppingController(est, epsilon=-1.0)


def test_stopping_controller_reset_clears_latch_and_observations():
    rng = np.random.default_rng(40)
    est = SubsampleEstimator("moments")
    ctl = StoppingController(est, epsilon=0.5, min_tasks=8)
    for tid in range(32):
        est.observe(tid, _moments_partial(rng))
    assert ctl.should_stop()
    ctl.reset()                    # job-level restart discards the run
    assert not ctl.stopped and ctl.final is None
    assert est.tasks_in() == 0
    assert not ctl.should_stop()   # must re-converge from scratch


def test_sim_restart_resets_stopper_before_retry():
    # a worker dies under job-level recovery: the restart discards and
    # re-executes every completion, so the stopper must start over — a
    # stale latch (or stale observations) would drain the retry at its
    # first settlement with an answer thinner than the recorded claim.
    # Virtual time over a constant cost model: fully deterministic.
    from repro.core.scheduler import SimParams, SimWorker, simulate_job
    rng = np.random.default_rng(11)
    partials = {tid: _moments_partial(rng) for tid in range(64)}
    est = SubsampleEstimator("moments")
    stopper = ReplayStopper(est, epsilon=0.6, partials=partials,
                            min_tasks=8)
    tasks = [Task(i, (i,), 100.0) for i in range(64)]
    # convergence needs 8 completions (t=4ms at 2x1ms workers); the
    # failure at 3.5ms lands first, with the estimator partially fed
    workers = [SimWorker(0), SimWorker(1, fail_at=0.0035)]
    params = SimParams(exec_time=lambda t: 1e-3,
                       fetch_time=lambda t: 0.0)
    out = simulate_job(tasks, workers, params, SchedulerConfig(seed=0),
                       stopper=stopper)
    assert out.restarts == 1
    executed = {r.task_id for r in out.results}
    assert stopper.stopped                 # retry re-converged...
    # ...on its own completions: the claim covers only executed tasks
    assert stopper.final.tasks_in <= len(executed)
    assert len(executed) < len(tasks)      # and the retry still drained


def test_submit_rejects_bad_error_target_without_leaking_slot():
    samples, months = _dataset(64)
    with PlatformService(_spec()) as svc:
        handle = svc.register_dataset(samples, months)
        with pytest.raises(ValueError, match="epsilon"):
            svc.submit(handle, WL, epsilon=-1.0)
        with pytest.raises(ValueError, match="confidence"):
            svc.submit(handle, WL, epsilon=0.5, confidence=1.5)
        assert svc.stats()["jobs_active"] == 0     # nothing reserved
        ok = svc.submit(handle, WL, seed=0)        # service still healthy
        ok.result(timeout=300)
    assert ok.status == "done"


def test_replay_stopper_feeds_from_captured_partials():
    rng = np.random.default_rng(5)
    partials = {tid: _moments_partial(rng) for tid in range(32)}
    est = SubsampleEstimator("moments")
    stopper = ReplayStopper(est, epsilon=0.5, partials=partials,
                            min_tasks=8)
    fired_at = None
    for tid in range(32):
        stopper.on_complete(tid)
        if stopper.should_stop():
            fired_at = tid + 1
            break
    assert fired_at is not None and 8 <= fired_at < 32
    assert est.tasks_in() == fired_at


# -- reduce tree: estimate()/snapshot() under concurrency and cancellation ---


def test_tree_estimate_under_concurrent_leaf_arrival():
    n = 96
    rng = np.random.default_rng(6)
    partials = {tid: _moments_partial(rng) for tid in range(n)}
    est = SubsampleEstimator("moments")
    tree = StreamingReduceTree(n, estimator=est)
    stop_readers = threading.Event()
    seen_mid_estimate = []

    def reader():
        while not stop_readers.is_set():
            snap = tree.snapshot()          # non-destructive mid-flight
            e = tree.estimate()
            if e is not None and 0 < e.tasks_in < n:
                seen_mid_estimate.append(e.tasks_in)
            time.sleep(1e-4)

    readers = [threading.Thread(target=reader) for _ in range(2)]
    for th in readers:
        th.start()
    ids = list(range(n))
    chunks = [ids[i::4] for i in range(4)]

    def writer(chunk):
        for tid in chunk:
            tree.offer(tid, partials[tid])
            time.sleep(1e-5)

    writers = [threading.Thread(target=writer, args=(c,)) for c in chunks]
    for th in writers:
        th.start()
    for th in writers:
        th.join()
    root = tree.result(timeout=30.0)
    stop_readers.set()
    for th in readers:
        th.join()
    # the full reduce is exact whatever the arrival interleaving
    expect = sum(float(np.asarray(partials[t]["count"])) for t in ids)
    assert float(np.asarray(root["count"])) == expect
    final = tree.estimate()
    assert final.tasks_in == n and np.isfinite(final.half_width)


def test_tree_estimate_deterministic_for_arrival_set():
    n = 40
    rng = np.random.default_rng(7)
    partials = {tid: _moments_partial(rng) for tid in range(n)}
    subset = sorted({1, 5, 8, 13, 21, 34, 2, 3})
    snaps = []
    for order in (subset, list(reversed(subset))):
        est = SubsampleEstimator("moments")
        tree = StreamingReduceTree(n, estimator=est)
        for tid in order:
            tree.offer(tid, partials[tid])
        tree.wait_leaves(len(subset), timeout=10.0)
        snaps.append((tree.snapshot(), tree.estimate()))
        tree.close()
    (root_a, est_a), (root_b, est_b) = snaps
    for k in root_a:
        assert np.array_equal(root_a[k], root_b[k])
    assert np.array_equal(est_a.value, est_b.value)
    assert est_a.half_width == est_b.half_width


def test_tree_mid_job_cancellation_finalizes_executed_subset():
    n = 64
    rng = np.random.default_rng(8)
    partials = {tid: _moments_partial(rng) for tid in range(n)}
    executed = list(range(20))
    tree = StreamingReduceTree(n, estimator=SubsampleEstimator("moments"))
    for tid in executed:
        tree.offer(tid, partials[tid])
    tree.wait_leaves(len(executed), timeout=10.0)
    root = tree.snapshot()
    tree.close()                            # DRAINING: rest never arrives
    assert float(np.asarray(root["count"])) == 100.0 * len(executed)
    # the synchronous subset combine reproduces the live tree bitwise
    ref = StreamingReduceTree.combine_subset(
        n, {tid: partials[tid] for tid in executed})
    for k in root:
        assert np.array_equal(root[k], ref[k])
    # waiting for leaves that will never arrive times out cleanly
    with pytest.raises(TimeoutError):
        tree.wait_leaves(len(executed) + 1, timeout=0.3)


def test_combine_subset_is_order_independent():
    n = 33
    rng = np.random.default_rng(9)
    partials = {tid: _moments_partial(rng) for tid in range(n)}
    ids = [0, 7, 31, 12, 3, 19]
    a = StreamingReduceTree.combine_subset(
        n, {t: partials[t] for t in ids})
    b = StreamingReduceTree.combine_subset(
        n, {t: partials[t] for t in reversed(ids)})
    for k in a:
        assert np.array_equal(a[k], b[k])


# -- scheduler cancel plumbing ----------------------------------------------


def test_two_phase_cancel_pending_drains():
    tasks = [Task(i, (i,), 100.0) for i in range(16)]
    sched = TwoPhaseScheduler(2, tasks, SchedulerConfig(seed=0))
    sched.initial_assignments()
    t0 = sched.on_worker_idle(0)
    sched.on_task_start(0, t0)
    dropped = sched.cancel_pending()
    assert len(dropped) == 15 and sched.cancelled_tasks == 15
    assert not sched.done()                 # t0 still in flight
    assert sched.on_worker_idle(1) is None  # nothing left to hand out
    from repro.core.scheduler import TaskResult
    sched.on_task_complete(TaskResult(t0.task_id, 0, 0.0, 0.0, 1e-3))
    assert sched.done()
    assert sched.cancel_pending() == []     # idempotent


# -- driver end-to-end -------------------------------------------------------


@pytest.mark.parametrize("backend", ["threaded", "simulated"])
def test_early_stop_executes_fewer_tasks(backend):
    samples, months = _dataset(256)
    full = Platform(_spec(backend=backend)).run(samples, months, WL)
    rep = Platform(_spec(backend=backend, epsilon=0.6, min_tasks=8)).run(
        samples, months, WL)
    assert rep.n_tasks == full.n_tasks == 128
    assert rep.stop_reason is not None and "converged" in rep.stop_reason
    assert 8 <= rep.tasks_executed < rep.n_tasks
    assert rep.tasks_cancelled == rep.n_tasks - rep.tasks_executed
    # the partial answer covers exactly the executed tasks
    assert float(rep.result["count"]) == float(
        WL.draws * WL.draw_size * rep.tasks_executed)
    # ...and the full-run answer lies inside the reported band
    ci = rep.final_ci
    full_mean = np.asarray(full.result["mean"], np.float64)
    assert bool(np.all((full_mean >= ci["ci_low"])
                       & (full_mean <= ci["ci_high"])))


@pytest.mark.parametrize("backend", ["threaded", "simulated"])
def test_epsilon_none_bit_identical(backend):
    samples, months = _dataset(96)
    base = Platform(_spec(backend=backend)).run(samples, months, WL)
    explicit = Platform(_spec(backend=backend, epsilon=None)).run(
        samples, months, WL)
    for k in ("mean", "var", "count"):
        assert np.array_equal(base.result[k], explicit.result[k])
    assert base.tasks_cancelled == explicit.tasks_cancelled == 0
    assert base.stop_reason is None and base.final_ci is None


def test_unconverged_epsilon_runs_to_completion_with_ci():
    samples, months = _dataset(64)
    rep = Platform(_spec(backend="simulated", epsilon=1e-12)).run(
        samples, months, WL)
    assert rep.stop_reason is None
    assert rep.tasks_executed == rep.n_tasks and rep.tasks_cancelled == 0
    # the band is still reported (full-data half-width)
    assert rep.final_ci is not None and rep.final_ci["tasks_in"] == \
        rep.n_tasks
    base = Platform(_spec(backend="simulated")).run(samples, months, WL)
    for k in ("mean", "var", "count"):
        assert np.array_equal(base.result[k], rep.result[k])


def test_epsilon_rejected_without_computed_values():
    with pytest.raises(ValueError, match="compute_values"):
        Platform(_spec(backend="simulated", epsilon=0.5,
                       compute_values=False)).run(*_dataset(32), WL)


# -- service end-to-end ------------------------------------------------------


def test_service_early_stop_frees_capacity_for_peers():
    samples, months = _dataset(256)
    spec = _spec()
    solo = Platform(_spec(seed=1)).run(samples, months, WL)
    with PlatformService(spec) as svc:
        handle = svc.register_dataset(samples, months)
        svc.submit(handle, WL, seed=99).result(timeout=300)   # warm class
        eps = svc.submit(handle, WL, seed=0, epsilon=0.6, min_tasks=8)
        peer = svc.submit(handle, WL, seed=1)
        r_eps = eps.result(timeout=300)
        r_peer = peer.result(timeout=300)
    assert eps.status == "done"
    assert eps.tasks_cancelled > 0
    assert eps.tasks_executed + eps.tasks_cancelled == eps.n_tasks
    assert "converged" in eps.stop_reason
    assert float(r_eps["count"]) == float(
        WL.draws * WL.draw_size * eps.tasks_executed)
    assert eps.final_ci is not None and \
        eps.final_ci["tasks_in"] >= 8
    # the peer is untouched: bit-identical to a standalone run
    assert peer.tasks_cancelled == 0
    for k in ("mean", "var", "count"):
        assert np.array_equal(r_peer[k], solo.result[k])


def test_service_epsilon_defaults_from_spec():
    samples, months = _dataset(128)
    with PlatformService(_spec(epsilon=0.6, min_tasks=8)) as svc:
        handle = svc.register_dataset(samples, months)
        svc.submit(handle, WL, seed=99).result(timeout=300)
        dflt = svc.submit(handle, WL, seed=0)          # inherits epsilon
        forced = svc.submit(handle, WL, seed=0, epsilon=None)  # exact
        dflt.result(timeout=300)
        forced.result(timeout=300)
    assert dflt.epsilon == 0.6 and dflt.tasks_cancelled > 0
    assert forced.epsilon is None and forced.tasks_cancelled == 0
    assert forced.tasks_executed == forced.n_tasks


def test_service_simulated_early_stop():
    samples, months = _dataset(256)
    with PlatformService(_spec(backend="simulated")) as svc:
        handle = svc.register_dataset(samples, months)
        t = svc.submit(handle, WL, seed=0, epsilon=0.6, min_tasks=8)
        res = t.result(timeout=300)
    assert t.tasks_cancelled > 0 and t.tasks_executed < t.n_tasks
    assert "converged" in t.stop_reason
    assert float(res["count"]) == float(
        WL.draws * WL.draw_size * t.tasks_executed)


def test_partial_returns_estimate_snapshot():
    samples, months = _dataset(96)
    with PlatformService(_spec()) as svc:
        handle = svc.register_dataset(samples, months)
        t = svc.submit(handle, WL, seed=0)
        res = t.result(timeout=300)
        p = t.partial()
    assert isinstance(p, PartialEstimate)
    assert {"value", "ci_low", "ci_high", "half_width", "tasks_in",
            "n_tasks", "confidence", "estimate"} <= set(p)
    assert set(p["estimate"]) == {"mean", "var", "count"}
    assert np.array_equal(p["estimate"]["mean"], res["mean"])
    # the legacy top-level statistic keys were retired after their
    # deprecation cycle: only the snapshot shape remains
    with pytest.raises(KeyError):
        p["mean"]
    with pytest.raises(KeyError):
        p["no_such_key"]


def test_partial_streams_ci_while_running():
    samples, months = _dataset(256)
    with PlatformService(_spec(n_workers=1)) as svc:
        handle = svc.register_dataset(samples, months)
        svc.submit(handle, WL, seed=9).result(timeout=300)
        t = svc.submit(handle, WL, seed=1)
        saw_ci = False
        for _ in range(2000):
            p = t.partial()
            if p is not None and p["tasks_in"] >= 2 and \
                    p["value"] is not None:
                assert np.isfinite(p["half_width"])
                assert p["tasks_in"] <= p["n_tasks"]
                saw_ci = True
                break
            if t.status == "done":
                break
            time.sleep(1e-3)
        final = t.result(timeout=300)
    assert saw_ci or final is not None     # tiny jobs may finish first
