"""The Netflix monthly-mean query on the served path, against the plain
float64 reference, and the counters a deployment reads: the arena's
device bytes (``JobTicket.arena_bytes``) and the wave lanes dispatched
for a job's tasks, padding lanes included (``JobTicket.wave_lanes``).

The data set is Netflix-shaped at a small size: rows of varied length
(lognormal ratings per movie with their months), 8 movies a task, so a
job's 12 tasks fall in three shape buckets, its last task repeats rows,
and its waves carry padding lanes."""

import json
import pathlib
import types
from collections import Counter

import numpy as np
import pytest

from bench import data, reference
from repro.core import scheduler as sch
from repro.core.subsample import SubsampleWorkload
from repro.platform import PlatformService, PlatformSpec, WaveOptions
from repro.platform.service import JobTicket

ROOT = pathlib.Path(__file__).resolve().parents[1]
QUERY = {"name": "netflix_low", "statistic": "monthly_mean", "draws": 8,
         "draw_size": 32, "grid": 120}
WL = SubsampleWorkload(QUERY["name"], QUERY["statistic"],
                       draws=QUERY["draws"], draw_size=QUERY["draw_size"],
                       grid=QUERY["grid"])
SEEDS = (3, 1000, 77_777)
# float32 sums, in scatter order, of at most a few thousand ratings in
# [1, 5] a bin, against float64: about 2e-7 of the largest mean at this
# size; a bfloat16 sum is off by about 1e-2
RTOL = 1e-5


def netflix(n_movies: int, seed: int):
    """A small Netflix-shaped data set and a knee of 8 movies a task."""
    cfg = json.loads(
        (ROOT / "bench" / "configs" / "netflix-prize.json").read_text())
    cfg.update(n_movies=n_movies, mean_ratings=48, min_ratings=8)
    ds = data.generate(cfg, seed)
    return ds, 8 * float(np.mean(ds.lengths * 4))


@pytest.fixture(scope="module")
def served():
    """Three concurrent jobs over one query class: ``(dataset, knee,
    handle's query class, tickets, results, the waves dispatched)``."""
    ds, knee = netflix(90, 7)
    with PlatformService(PlatformSpec(knee_bytes=knee,
                                      telemetry=True)) as svc:
        handle = svc.register_dataset(ds.samples, ds.months, knee_bytes=knee)
        tickets = [svc.submit(handle, WL, seed=s) for s in SEEDS]
        results = [t.result(timeout=300) for t in tickets]
        waves = [e.fields for e in svc.telemetry.events("wave_dispatched")]
    (qc,) = handle._classes.values()
    return ds, knee, qc, tickets, results, waves


def serve_alone(seed: int):
    """One job alone on a fresh service over the fixture's data set:
    ``(query class, ticket, result, the service's stats)``."""
    ds, knee = netflix(90, 7)
    with PlatformService(PlatformSpec(knee_bytes=knee)) as svc:
        handle = svc.register_dataset(ds.samples, ds.months, knee_bytes=knee)
        ticket = svc.submit(handle, WL, seed=seed)
        result = ticket.result(timeout=300)
        stats = svc.stats()
    (qc,) = handle._classes.values()
    return qc, ticket, result, stats


@pytest.fixture(scope="module")
def alone():
    return serve_alone(SEEDS[0])


def bucket_lanes(qc) -> int:
    """The lanes a lone job dispatches when each claim fills its wave
    from the whole queue: every bucket in ``ceil(n / cap)`` full-width
    waves."""
    sizes = Counter(qc.plan.task_shape(t) for t in qc.plan.tasks)
    return sum(-(-n // qc.wave_ctx.wave_pad[key]) * qc.wave_ctx.wave_pad[key]
               for key, n in sizes.items())


def test_the_data_set_spans_shape_buckets_and_padded_lanes(served, alone):
    ds, knee, qc, _, _, _ = served
    groups = reference.task_groups(ds.lengths, knee)
    assert len(groups[-1]) < len(groups[0])           # a task repeats rows
    assert qc.wave_ctx is not None and qc.engine == "jnp"
    assert len(qc.wave_ctx.arena.keys()) >= 2
    qc, ticket, _, _ = alone
    assert ticket.wave_lanes == bucket_lanes(qc) > ticket.n_tasks


def test_a_lone_job_fills_each_bucket_from_the_whole_queue(alone):
    ds, knee = netflix(90, 7)
    qc, ticket, got, stats = alone
    # the buckets alternate in task order: a claim reached past another
    # bucket's task for some of its wave
    assert stats["tasks_pulled_forward"] > 0
    assert ticket.wave_lanes == bucket_lanes(qc)
    want = reference.job_result(ds, knee, ticket.seed, QUERY)
    assert np.array_equal(np.asarray(got["count"], np.float64),
                          want["count"])
    np.testing.assert_allclose(
        np.asarray(got["monthly_mean"], np.float64), want["monthly_mean"],
        rtol=0, atol=RTOL * np.max(np.abs(want["monthly_mean"])))


def test_grouped_claims_give_the_head_run_orders_bits(alone, monkeypatch):
    def head_run(self, job, key, room):
        taken = []
        while (job.pending and len(taken) < room
               and job.fuse_key(job.pending[0]) == key):
            t = job.pending.popleft()
            if t.task_id not in job.completed_ids:
                taken.append(t)
        return taken

    monkeypatch.setattr(sch.MultiJobScheduler, "_take_matching", head_run)
    _, grouped, want, _ = alone
    _, ticket, got, stats = serve_alone(grouped.seed)
    assert stats["tasks_pulled_forward"] == 0
    assert ticket.wave_lanes > grouped.wave_lanes     # the orders differ
    for name in ("monthly_mean", "count"):
        assert np.array_equal(np.asarray(got[name]), np.asarray(want[name]))


@pytest.mark.parametrize("job", range(len(SEEDS)))
def test_monthly_mean_matches_the_float64_reference(served, job):
    ds, knee, _, tickets, results, _ = served
    want = reference.job_result(ds, knee, tickets[job].seed, QUERY)
    got = results[job]
    assert np.array_equal(np.asarray(got["count"], np.float64),
                          want["count"])
    np.testing.assert_allclose(
        np.asarray(got["monthly_mean"], np.float64), want["monthly_mean"],
        rtol=0, atol=RTOL * np.max(np.abs(want["monthly_mean"])))


def test_arena_bytes_is_the_arenas_device_bytes_for_every_job(served):
    _, _, qc, tickets, _, _ = served
    arena = qc.wave_ctx.arena
    device = 0
    for key in arena.keys():
        planes, months = arena.bucket(key)
        device += planes.nbytes + months.nbytes
    assert device > 0
    assert [t.arena_bytes for t in tickets] == [device] * len(tickets)
    assert all(t.stats()["arena_bytes"] == device for t in tickets)


def test_wave_lanes_sum_to_the_lanes_dispatched(served):
    _, _, qc, tickets, _, waves = served
    tasks = {t.task_id: t for t in qc.plan.tasks}
    dispatched = sum(
        max(qc.cap(tasks[w["task_ids"][0]]), w["wave_size"]) for w in waves)
    assert sum(t.wave_lanes for t in tickets) == pytest.approx(dispatched)
    assert dispatched > sum(t.n_tasks for t in tickets)


def test_a_fused_wave_splits_its_lanes_by_task_share():
    ds, knee = netflix(90, 8)
    with PlatformService(PlatformSpec(knee_bytes=knee)) as svc:
        handle = svc.register_dataset(ds.samples, ds.months, knee_bytes=knee)
        svc.submit(handle, WL, seed=0).result(timeout=300)
        (qc,) = handle._classes.values()
        bucket = max(({qc.plan.task_shape(t) for t in qc.plan.tasks}),
                     key=lambda k: qc.wave_ctx.wave_pad[k])
        same = [t for t in qc.plan.tasks if qc.plan.task_shape(t) == bucket]
        pad = qc.wave_ctx.wave_pad[bucket]
        assert pad >= 4
        a, b = (JobTicket(job_id, handle, WL, len(qc.plan.tasks),
                          WL.statistic, seed=job_id)
                for job_id in (10_001, 10_002))
        svc._tickets.update({a.job_id: a, b.job_id: b})
        pa, pb = (types.SimpleNamespace(job_id=t.job_id, seed=t.seed)
                  for t in (a, b))
        out = svc._class_run_batch(qc)(
            [(pa, same[0]), (pa, same[1]), (pb, same[2])])
    assert len(out) == 3
    assert a.wave_lanes == pytest.approx(pad * 2 / 3)
    assert b.wave_lanes == pytest.approx(pad / 3)
    assert a.wave_lanes + b.wave_lanes == pytest.approx(pad)
    assert a.device_dispatches == b.device_dispatches == 1


def test_a_class_without_waves_reports_no_arena_and_no_lanes():
    ds, knee = netflix(24, 9)
    with PlatformService(PlatformSpec(knee_bytes=knee,
                                      waves=WaveOptions(wave="off"))) as svc:
        handle = svc.register_dataset(ds.samples, ds.months, knee_bytes=knee)
        ticket = svc.submit(handle, WL, seed=5)
        ticket.result(timeout=300)
    assert ticket.arena_bytes == 0.0 and ticket.wave_lanes == 0.0
    assert ticket.device_dispatches == ticket.n_tasks
