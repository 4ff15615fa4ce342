"""The least work of ``grid_stat_roofline``, counted by hand, and the
three readers this deployment added: ``grid_stat_roofline``,
``arena_over_true_bytes`` and ``wave_lane_fill``."""

import json
import pathlib
import types

import numpy as np
import pytest

from bench import data, grid_roofline, harness, reference, roofline, tracing
from conftest import ROOT

QUERY = {"name": "netflix_low", "statistic": "monthly_mean", "draws": 1,
         "draw_size": 3, "grid": 120}
# per bucket width, the columns drawn in every row (draws=1, 2 rows, 3)
COLUMNS = {
    # task 0, samples 0 (3 ratings) and 1 (6): row 0 draws observations
    # 0, 0 and 1 (padded column 3 wraps to 0), row 1 observations 1, 1
    # and 5 (padded column 7 wraps to 1): 4 distinct
    8: [[[0, 3, 1], [7, 1, 5]]],
    # task 1, sample 2 (2 ratings) in both rows (a repeated row): 2
    2: [[[0, 1, 1], [1, 0, 0]]],
}


def tiny_dataset() -> data.Dataset:
    lengths = np.asarray([3, 6, 2])
    offsets = np.concatenate([[0], np.cumsum(lengths)[:-1]])
    total = int(lengths.sum())
    return data.Dataset({}, {}, np.ones(total, np.float32),
                        np.zeros(total, np.int32), lengths, offsets)


@pytest.fixture
def fixed_draws(monkeypatch):
    monkeypatch.setattr(
        reference, "draw", lambda seeds, shape, maxval: np.broadcast_to(
            np.asarray(COLUMNS[maxval]), (len(seeds),) + tuple(shape)))


def test_least_work_counts_distinct_observations_once_per_task(
        fixed_draws):
    ds = tiny_dataset()
    knee = 2 * float(np.mean(ds.lengths * 4))        # tasks [0, 1], [2]
    nbytes, flops = grid_roofline.grid_least_work(ds, knee, [11, 12], QUERY)
    assert nbytes == 2 * (4 + 2) * (4 + 4)           # two jobs, rating+month
    assert flops == 2 * (2 * 6) * grid_roofline.GRID_FLOPS_PER_VALUE


def test_least_work_without_a_months_plane_counts_the_values_alone(
        fixed_draws):
    ds = tiny_dataset()
    ds.month_values = None
    knee = 2 * float(np.mean(ds.lengths * 4))
    nbytes, _ = grid_roofline.grid_least_work(ds, knee, [11], QUERY)
    assert nbytes == (4 + 2) * 4


def test_least_bytes_never_exceed_what_the_draws_read():
    """With real draws, on a small Netflix-shaped data set: at most one
    observation per drawn value, and at most each task's true bytes."""
    cfg = json.loads(
        (ROOT / "bench" / "configs" / "netflix-prize.json").read_text())
    cfg.update(n_movies=40, mean_ratings=24, min_ratings=4)
    ds = data.generate(cfg, 5)
    knee = 8 * float(np.mean(ds.lengths * 4))
    query = dict(QUERY, draws=8, draw_size=32)
    nbytes, flops = grid_roofline.grid_least_work(ds, knee, [3, 4], query)
    drawn = flops / grid_roofline.GRID_FLOPS_PER_VALUE
    assert 0 < nbytes <= drawn * 8
    assert nbytes <= 2 * (ds.values.nbytes + ds.month_values.nbytes)


def job(seed: int, **counters):
    return types.SimpleNamespace(seed=seed, ticket=types.SimpleNamespace(
        n_tasks=counters.pop("n_tasks", 3), **counters))


def run_of(jobs, ds, knee, trace=None, query=QUERY):
    return harness.Run(jobs=jobs, window_s=1.0, first_query_s=0.1,
                       setup_s=1.0, dataset=ds, knee_bytes=knee,
                       query=query, peaks=roofline.peaks("TPU v5 lite"),
                       trace=trace)


def busy_trace(seconds: float) -> tracing.TraceSummary:
    return tracing.TraceSummary(window_s=2 * seconds, busy_s=seconds,
                                op_seconds={}, op_detail={}, idle_gaps=[])


def test_roofline_is_the_least_time_over_the_busy_union(fixed_draws):
    ds = tiny_dataset()
    knee = 2 * float(np.mean(ds.lengths * 4))
    nbytes, flops = grid_roofline.grid_least_work(ds, knee, [11], QUERY)
    least, bound = roofline.least_seconds(
        nbytes, flops, roofline.peaks("TPU v5 lite"))
    assert bound == "hbm"
    read = harness.load_reader(ROOT / "bench", "grid_stat_roofline")
    # a device busy for exactly the least time reads 100%, four times
    # as long 25%
    assert read(run_of([job(11)], ds, knee, busy_trace(least))) == (
        pytest.approx(100.0))
    assert read(run_of([job(11)], ds, knee, busy_trace(4 * least))) == (
        pytest.approx(25.0))


def test_roofline_on_a_recorded_chip_trace_stays_within_100(fixed_draws):
    rec = json.loads((pathlib.Path(__file__).parent / "data"
                      / "chip_trace.json").read_text())
    summary = tracing.summarize(tracing.TraceData(
        {p: [tuple(e) for e in ev] for p, ev in rec["devices"].items()},
        [tuple(s) for s in rec["spans"]]))
    ds = tiny_dataset()
    knee = 2 * float(np.mean(ds.lengths * 4))
    read = harness.load_reader(ROOT / "bench", "grid_stat_roofline")
    value = read(run_of([job(11), job(12)], ds, knee, summary))
    assert 0 < value <= 100


@pytest.mark.parametrize("metric", ["grid_stat_roofline",
                                    "arena_over_true_bytes",
                                    "wave_lane_fill"])
def test_readers_read_nothing_where_the_counters_are_missing(metric):
    """The parent commit's tickets lack ``arena_bytes`` and
    ``wave_lanes``; an untraced run has no trace; a moments query is no
    grid statistic."""
    ds = tiny_dataset()
    knee = 2 * float(np.mean(ds.lengths * 4))
    read = harness.load_reader(ROOT / "bench", metric)
    assert read(run_of([job(1), job(2)], ds, knee)) is None
    assert read(run_of([], ds, knee, busy_trace(1.0))) is None
    if metric == "grid_stat_roofline":
        moments = dict(QUERY, statistic="moments")
        assert read(run_of([job(1)], ds, knee, busy_trace(1.0),
                           moments)) is None


def test_counter_readers_read_the_tickets():
    ds = tiny_dataset()
    true = ds.values.nbytes + ds.month_values.nbytes
    jobs = [job(1, arena_bytes=4.0 * true, wave_lanes=4.0),
            job(2, arena_bytes=4.0 * true, wave_lanes=2.0),
            job(3, n_tasks=2, arena_bytes=4.0 * true, wave_lanes=2.0)]
    run = run_of(jobs, ds, 1.0)
    bench = ROOT / "bench"
    assert harness.load_reader(bench, "arena_over_true_bytes")(run) == 4.0
    assert harness.load_reader(bench, "wave_lane_fill")(run) == (
        pytest.approx(100.0 * 8 / 8))
    jobs[0].ticket.wave_lanes = 8.0
    assert harness.load_reader(bench, "wave_lane_fill")(run) == (
        pytest.approx(100.0 * 8 / 12))
    for j in jobs:
        j.ticket.arena_bytes = j.ticket.wave_lanes = 0.0
    assert harness.load_reader(bench, "arena_over_true_bytes")(run) is None
    assert harness.load_reader(bench, "wave_lane_fill")(run) is None


def test_arena_ratio_counts_the_values_alone_without_months():
    ds = tiny_dataset()
    ds.month_values = None
    run = run_of([job(1, arena_bytes=2.0 * ds.values.nbytes,
                      wave_lanes=4.0)], ds, 1.0)
    assert harness.load_reader(ROOT / "bench",
                               "arena_over_true_bytes")(run) == 2.0
