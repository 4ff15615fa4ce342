"""End-to-end tests for the ``repro.platform`` driver: backend parity
(bit-identical statistics), report structure, kneepoint working-set
bounds, the streaming reduce tree, and engine fallbacks."""

import threading

import numpy as np
import pytest

from repro.core import subsample as ss
from repro.core.datastore import ReplicatedDataStore, ReplicationPolicy
from repro.core.scheduler import SimWorker
from repro.platform import (
    MOMENTS,
    Platform,
    PlatformSpec,
    StreamingReduceTree,
    finalize_stats,
    make_tasks,
    measure_per_sample_cost,
)
from repro.data.synthetic import (
    EagletSpec,
    NetflixSpec,
    eaglet_dataset,
    netflix_dataset,
)

KNEE = 4 * 1024 * 4


@pytest.fixture(scope="module")
def netflix():
    return netflix_dataset(NetflixSpec(n_movies=24, mean_ratings=1024))


# -- backend parity -----------------------------------------------------------

@pytest.mark.parametrize("workload", [ss.NETFLIX_HIGH, MOMENTS],
                         ids=["monthly_mean", "moments"])
def test_threaded_and_simulated_backends_bit_identical(netflix, workload):
    """Same seed + same engine + deterministic reduce tree ⇒ the two
    backends must agree to the last bit, at different worker counts."""
    samples, months = netflix
    threaded = Platform(PlatformSpec(
        platform="BTS", n_workers=3, backend="threaded",
        knee_bytes=KNEE, seed=11)).run(samples, months, workload)
    simulated = Platform(PlatformSpec(
        platform="BTS", n_workers=7, backend="simulated",
        knee_bytes=KNEE, seed=11)).run(samples, months, workload)
    assert threaded.result is not None and simulated.result is not None
    for key in threaded.result:
        np.testing.assert_array_equal(
            np.asarray(threaded.result[key]),
            np.asarray(simulated.result[key]),
            err_msg=f"backends diverged on {key!r}")


def test_simulated_backend_with_heterogeneous_workers_same_stats(netflix):
    samples, months = netflix
    base = Platform(PlatformSpec(
        platform="BTS", n_workers=2, backend="threaded",
        knee_bytes=KNEE, seed=5)).run(samples, months, ss.NETFLIX_HIGH)
    hetero = Platform(PlatformSpec(
        platform="BTS", backend="simulated", knee_bytes=KNEE, seed=5,
        sim_workers=tuple(SimWorker(i, speed=1.0 if i % 2 else 0.5)
                          for i in range(6)))).run(samples, months,
                                                   ss.NETFLIX_HIGH)
    np.testing.assert_array_equal(base.result["monthly_mean"],
                                  hetero.result["monthly_mean"])
    assert hetero.makespan > 0


# -- report structure ---------------------------------------------------------

def test_job_report_phases_populated(netflix):
    samples, months = netflix
    rep = Platform(PlatformSpec(
        platform="BTS", n_workers=2, backend="threaded",
        knee_bytes=KNEE)).run(samples, months, ss.NETFLIX_HIGH)
    for phase in ("plan", "distribute", "compile", "execute", "reduce"):
        assert phase in rep.phases, rep.phases
        assert rep.phases[phase] >= 0.0
    # execute must dominate a knee-supplied job and include startup
    assert rep.phases["execute"] > 0
    assert rep.makespan >= rep.startup_time
    assert rep.queue_depths, "dynamic-k trace missing"
    assert rep.reduce_info is not None and rep.reduce_info["combines"] >= 0
    assert rep.backend == "threaded" and rep.engine == "jnp"
    assert rep.throughput_bps > 0


def test_offline_kneepoint_phase_charged_and_curve_reported(netflix):
    samples, months = netflix
    rep = Platform(PlatformSpec(
        platform="BTS", n_workers=2, backend="threaded",
        kneepoint_sizes=(1, 2, 4, 8))).run(samples, months,
                                           ss.NETFLIX_HIGH)
    assert rep.kneepoint is not None
    assert rep.phases["plan"] > 0            # offline phase actually ran
    assert len(rep.miss_curve) >= 2          # cache-proxy miss curve
    assert rep.task_size_bytes == rep.kneepoint.task_size


def test_kneepoint_task_size_bounds_working_set():
    """Every task's working set must stay within the knee (plus one mean
    sample of count-rounding slack)."""
    sample_bytes = 512 * 4
    samples = {i: np.zeros(512, np.float32) for i in range(64)}
    months = {i: np.zeros(512, np.int32) for i in range(64)}
    knee = 8 * sample_bytes
    rep = Platform(PlatformSpec(
        platform="BTS", n_workers=2, backend="simulated",
        knee_bytes=knee)).run(samples, months, ss.NETFLIX_LOW)
    assert rep.max_task_bytes <= knee + sample_bytes
    assert rep.n_tasks == 8                  # 64 samples / 8 per task


def test_make_tasks_partitions_every_sizing():
    sizes = [100] * 37
    for sizing, knee in (("tiny", None), ("large", None),
                         ("kneepoint", 400)):
        tasks = make_tasks(sizes, sizing, knee, 4)
        flat = sorted(i for t in tasks for i in t.sample_ids)
        assert flat == list(range(37)), sizing


# -- datastore integration ----------------------------------------------------

def test_datastore_feedback_and_stats_in_report(netflix):
    samples, months = netflix
    store = ReplicatedDataStore(
        n_initial=1, policy=ReplicationPolicy(fetch_slo=2e-3))
    rep = Platform(PlatformSpec(
        platform="BTS", n_workers=2, backend="threaded",
        knee_bytes=KNEE), datastore=store).run(samples, months,
                                               ss.NETFLIX_HIGH)
    assert rep.datastore_stats is not None
    assert rep.datastore_stats["replicas"] >= 1
    assert store._exec_ema is not None       # scheduler feedback arrived


# -- scale-out entry ----------------------------------------------------------

def test_run_scaleout_throughput_scales_with_workers():
    per_sample = 2e-4
    tp = {}
    for cores in (4, 16):
        rep = Platform(PlatformSpec(
            platform="BTS", n_workers=cores, backend="simulated",
            knee_bytes=8 * 2048,
            startup_time=0.005)).run_scaleout(   # large-job linear region
                [2048] * 2048, per_sample_exec=per_sample)
        assert rep.result is None            # cost-model mode: no stats
        tp[cores] = rep.throughput_bps
    assert tp[16] > 2.5 * tp[4]


# -- reduce tree --------------------------------------------------------------

def test_reduce_tree_order_independent_and_exact():
    rng = np.random.default_rng(0)
    parts = [{"sum": rng.normal(size=16).astype(np.float32),
              "count": np.float32(1)} for _ in range(13)]

    def run_order(order):
        tree = StreamingReduceTree(len(parts))
        for i in order:
            tree.offer(i, parts[i])
        return tree.result(timeout=30)

    a = run_order(range(13))
    b = run_order(reversed(range(13)))
    c = run_order(np.random.default_rng(3).permutation(13))
    np.testing.assert_array_equal(a["sum"], b["sum"])
    np.testing.assert_array_equal(a["sum"], c["sum"])
    assert a["count"] == 13


def test_finalize_stats_moments():
    root = {"sum": np.asarray([10.0, 0.0]), "sumsq": np.asarray([30.0, 4.0]),
            "count": np.asarray(10.0)}
    out = finalize_stats(root, "moments")
    np.testing.assert_allclose(out["mean"], [1.0, 0.0])
    np.testing.assert_allclose(out["var"], [2.0, 0.4])


# -- engines ------------------------------------------------------------------

def test_numpy_engine_statistically_matches_jnp(netflix):
    samples, months = netflix
    spec = dict(platform="BTS", n_workers=2, backend="threaded",
                knee_bytes=KNEE, seed=0)
    jnp_rep = Platform(PlatformSpec(engine="jnp", **spec)).run(
        samples, months, ss.NETFLIX_HIGH)
    np_rep = Platform(PlatformSpec(engine="numpy", **spec)).run(
        samples, months, ss.NETFLIX_HIGH)
    a, b = jnp_rep.result["monthly_mean"], np_rep.result["monthly_mean"]
    valid = (np.asarray(jnp_rep.result["count"]) > 50) \
        & (np.asarray(np_rep.result["count"]) > 50)
    assert valid.sum() > 10
    assert np.mean(np.abs(a[valid] - b[valid])) < 0.25


def test_custom_map_fn_with_overhead_config():
    samples = {i: np.zeros(8, np.float32) for i in range(10)}
    months = {i: np.zeros(8, np.int32) for i in range(10)}
    calls = []

    def map_fn(task, block, mo, seed):
        calls.append(task.task_id)
        return {"count": np.asarray(1.0, np.float32)}

    rep = Platform(PlatformSpec(platform="VH", n_workers=1,
                                backend="threaded", task_sizing="tiny"),
                   map_fn=map_fn).run(samples, months, None)
    assert sorted(calls) == list(range(10))
    assert rep.n_tasks == 10
    assert rep.result["count"] == 10.0
    assert rep.engine == "custom"


def test_custom_map_fn_runs_each_task_once_on_the_pool():
    samples = {i: np.zeros(8, np.float32) for i in range(24)}
    months = {i: np.zeros(8, np.int32) for i in range(24)}
    calls = []
    lock = threading.Lock()

    def map_fn(task, block, mo, seed):
        with lock:
            calls.append(task.task_id)
        return {"count": np.asarray(1.0, np.float32)}

    rep = Platform(PlatformSpec(platform="BTT", n_workers=3,
                                backend="threaded"),
                   map_fn=map_fn).run(samples, months, None)
    assert sorted(calls) == list(range(24))
    assert rep.tasks_executed == rep.n_tasks == 24
    assert rep.result["count"] == 24.0


# -- eaglet end-to-end through the driver -------------------------------------

def test_eaglet_outliers_run_end_to_end():
    samples, months = eaglet_dataset(EagletSpec(n_families=24,
                                                mean_markers=512,
                                                heavy_tail=True))
    rep = Platform(PlatformSpec(
        platform="BTS", n_workers=2, backend="simulated",
        knee_bytes=8 * 512 * 4, seed=1)).run(samples, months, ss.EAGLET)
    assert np.all(np.isfinite(rep.result["alod"]))
    assert rep.calibration_seconds > 0


def test_measure_per_sample_cost_positive(netflix):
    samples, months = netflix
    cost = measure_per_sample_cost(samples, months, ss.NETFLIX_LOW,
                                   block=4)
    assert 0 < cost < 1.0
