"""The trace reduction on small recorded traces."""

import json
import pathlib

import pytest

from bench import tracing

MS = 1_000_000  # ns


def trace():
    """A 100 ms window on one device: ops at [10, 30) and [20, 40)
    (overlapping), [60, 70) and [95, 110) (crossing the window's end);
    a client in ``bench.submit`` over [0, 45) and ``bench.result_wait``
    over [45, 100)."""
    ops = [("fusion.1", 10 * MS, 20 * MS, "jit_wave"),
           ("subsample_stats_wave", 20 * MS, 20 * MS, "jit_wave kernel"),
           ("fusion.1", 60 * MS, 10 * MS, "jit_wave"),
           ("copy", 95 * MS, 15 * MS, "")]
    spans = [("bench.window", 0, 100 * MS), ("bench.submit", 0, 45 * MS),
             ("bench.result_wait", 45 * MS, 55 * MS)]
    return tracing.TraceData({"/device:TPU:0": ops}, spans)


def test_union_merges_overlaps():
    assert tracing.union([(5, 9), (1, 3), (2, 4), (9, 10)]) == [
        (1, 4), (5, 10)]


def test_busy_is_the_union_inside_the_window():
    s = tracing.summarize(trace())
    assert s.window_s == pytest.approx(0.1)
    # [10, 40) + [60, 70) + [95, 100) = 45 ms
    assert s.busy_s == pytest.approx(0.045)


def test_op_seconds_and_kernel_time():
    s = tracing.summarize(trace())
    assert s.op_seconds["fusion.1"] == pytest.approx(0.030)
    assert s.seconds_of("subsample_stats_wave") == pytest.approx(0.020)
    assert s.seconds_of("kernel") == pytest.approx(0.020)   # by its stats
    assert s.device_ops()[0] == ["fusion.1", pytest.approx(0.030)]


def test_gaps_go_to_the_span_the_host_was_in():
    s = tracing.summarize(trace())
    # gap [0, 10) lies in submit; [40, 60) mostly in result_wait, and
    # [70, 95) wholly
    got = dict((name, sec) for name, sec in s.idle_gaps)
    assert got == {"bench.submit": pytest.approx(0.010),
                   "bench.result_wait": pytest.approx(0.045)}
    assert s.idle_gaps[0][0] == "bench.result_wait"


def test_busy_is_averaged_over_devices():
    t = trace()
    t.devices["/device:TPU:1"] = [("fusion.2", 0, 100 * MS, "")]
    assert tracing.summarize(t).busy_s == pytest.approx((0.045 + 0.1) / 2)


def test_no_window_or_no_device_reads_nothing():
    t = trace()
    assert tracing.summarize(tracing.TraceData({}, t.spans)) is None
    assert tracing.summarize(tracing.TraceData(t.devices, [])) is None


RECORDED = pathlib.Path(__file__).parent / "data" / "chip_trace.json"


def test_recorded_chip_trace():
    """The first 30 ms of a traced window on a TPU v5e, against an
    occupancy grid of one microsecond."""
    rec = json.loads(RECORDED.read_text())
    t = tracing.TraceData(
        {p: [tuple(e) for e in ev] for p, ev in rec["devices"].items()},
        [tuple(s) for s in rec["spans"]])
    s = tracing.summarize(t)
    assert s.window_s == pytest.approx(rec["expect"]["window_s"])
    assert s.busy_s == pytest.approx(rec["expect"]["busy_s"],
                                     abs=rec["expect"]["busy_tol"])
    assert s.seconds_of(rec["expect"]["kernel"]) == pytest.approx(
        rec["expect"]["kernel_s"])
    assert sum(sec for _, sec in s.idle_gaps) == pytest.approx(
        s.window_s - s.busy_s)
    assert {name for name, _ in s.idle_gaps} <= {"bench.submit",
                                                 "bench.result_wait"}
