"""The program's spans in a trace (``program_spans``) and the three
readers of the per-job seconds the program charges to its spans."""

import json
import pathlib
from types import SimpleNamespace

import pytest

from bench import harness, program_spans, tracing, traffic

MS = 1_000_000  # ns
ROOT = pathlib.Path(__file__).resolve().parents[2]


def device():
    """One device busy over [10, 40), [60, 70) and [95, 110) ms."""
    return {"/device:TPU:0": [("fusion.1", 10 * MS, 30 * MS, ""),
                              ("fusion.1", 60 * MS, 10 * MS, ""),
                              ("copy", 95 * MS, 15 * MS, "")]}


BENCH_SPANS = [("bench.window", 0, 100 * MS), ("bench.submit", 0, 45 * MS),
               ("bench.result_wait", 45 * MS, 55 * MS)]


def test_program_spans_take_the_gaps_they_overlap():
    # gaps: [0, 10), [40, 60), [70, 95).  Inside the client's wait, one
    # worker fetches over [40, 58) and another uploads over [72, 90) on
    # a second thread; nothing of the program covers [0, 10)
    spans = BENCH_SPANS + [("platform.fetch_result", 40 * MS, 18 * MS),
                           ("platform.upload", 72 * MS, 18 * MS),
                           ("platform.claim", 70 * MS, 1 * MS)]
    got = dict(program_spans.breakdown(device(), spans)["idle_gaps"])
    assert got == {"bench.submit": pytest.approx(0.010),
                   "platform.fetch_result": pytest.approx(0.020),
                   "platform.upload": pytest.approx(0.025)}
    out = program_spans.breakdown(device(), spans)
    assert out["idle_in_program_spans"] == pytest.approx(0.045 / 0.055)


def test_span_totals_are_clipped_and_summed_over_threads():
    spans = [("platform.upload", -5 * MS, 10 * MS),        # 5 ms inside
             ("platform.upload", 20 * MS, 10 * MS),        # another thread
             ("platform.upload", 25 * MS, 10 * MS),
             ("platform.reduce", 95 * MS, 20 * MS),        # 5 ms inside
             ("platform.finalize", 120 * MS, 5 * MS)]      # outside
    seconds, counts = program_spans.span_totals(spans, 0, 100 * MS)
    assert seconds == {"platform.upload": pytest.approx(0.025),
                       "platform.reduce": pytest.approx(0.005)}
    assert counts == {"platform.upload": 3, "platform.reduce": 1}


def test_a_bench_only_trace_gives_the_harness_gaps():
    t = tracing.TraceData(device(), BENCH_SPANS)
    assert (program_spans.breakdown(t.devices, t.spans)["idle_gaps"]
            == tracing.summarize(t).idle_gaps)


def test_recorded_chip_trace_gives_the_harness_gaps():
    rec = json.loads((pathlib.Path(__file__).parent / "data"
                      / "chip_trace.json").read_text())
    t = tracing.TraceData(
        {p: [tuple(e) for e in ev] for p, ev in rec["devices"].items()},
        [tuple(s) for s in rec["spans"]])
    out = program_spans.breakdown(t.devices, t.spans)
    want = tracing.summarize(t)
    assert [n for n, _ in out["idle_gaps"]] == [n for n, _ in
                                                want.idle_gaps]
    for (_, got), (_, sec) in zip(out["idle_gaps"], want.idle_gaps):
        assert got == pytest.approx(sec)
    assert out["idle_in_program_spans"] == 0.0


# -- the readers --------------------------------------------------------------


def run_of(*span_seconds, trace=True):
    """A hand-built run of finished jobs whose tickets hold
    ``span_seconds`` (``None``: a ticket of a program without spans)."""
    jobs = [traffic.Job(i, i, 0.0, ticket=SimpleNamespace()
                        if s is None else SimpleNamespace(span_seconds=s))
            for i, s in enumerate(span_seconds)]
    summary = tracing.summarize(tracing.TraceData(device(), BENCH_SPANS))
    return harness.Run(jobs=jobs, window_s=1.0, first_query_s=0.0,
                       setup_s=0.0, dataset=None, knee_bytes=0.0, query={},
                       peaks=None, trace=summary if trace else None)


def reader(name):
    return harness.load_reader(ROOT / "bench", name)


JOBS = ({"platform.upload": 0.030, "platform.fetch_result": 0.002,
         "platform.reduce": 0.004, "platform.finalize": 0.001},
        {"platform.upload": 0.010, "platform.fetch_result": 0.004,
         "platform.reduce": 0.002, "platform.finalize": 0.003})


@pytest.mark.parametrize("name,want", [
    ("upload_ms_per_job", 20.0),
    ("result_fetch_ms_per_job", 3.0),
    ("host_reduce_ms_per_job", 5.0),
])
def test_reader_reads_ms_per_job(name, want):
    assert reader(name)(run_of(*JOBS)) == pytest.approx(want)


@pytest.mark.parametrize("name", ["upload_ms_per_job",
                                  "result_fetch_ms_per_job",
                                  "host_reduce_ms_per_job"])
def test_reader_reads_nothing_where_there_is_nothing(name):
    read = reader(name)
    assert read(run_of(*JOBS, trace=False)) is None
    assert read(run_of()) is None
    assert read(run_of(None, None)) is None        # a program without spans
    assert read(run_of({}, {"platform.claim": 0.1})) is None
