"""Unified telemetry (ISSUE 8): the event bus is off-by-default cheap
(disabled ⇒ zero recorded events, bit-identical results), deterministic
under the simulated backend, bounded under chaos, and its exported
Chrome trace / HTML report are well-formed without any dependency."""

import json
import os

import numpy as np
import pytest

from repro.core import scheduler as sch
from repro.core.subsample import SubsampleWorkload
from repro.platform import telemetry as tel
from repro.platform import (
    EVENT_KINDS,
    Event,
    MetricsRegistry,
    MomentsSpec,
    Platform,
    PlatformService,
    PlatformSpec,
    TelemetryBus,
    TelemetryConfig,
    TelemetrySampler,
    build_trace,
    null_bus,
    render_report,
    resolve_telemetry_config,
    write_trace,
)
from repro.platform.faults import FaultEvent, FaultInjector, FaultPlan

WL = MomentsSpec(draws=4, draw_size=16)
KNEE = 4 * 96 * 4


def _dataset(n=16, length=96, seed=0):
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


def _results_equal(a, b):
    return (set(a) == set(b)
            and all(np.array_equal(np.asarray(a[k]), np.asarray(b[k]))
                    for k in a))


# -- config resolution --------------------------------------------------------


def test_resolve_config_forms():
    assert resolve_telemetry_config(None).enabled is False
    assert resolve_telemetry_config(False).enabled is False
    assert resolve_telemetry_config(True).enabled is True
    assert resolve_telemetry_config("on").enabled is True
    cfg = TelemetryConfig(enabled=True, capacity=128)
    assert resolve_telemetry_config(cfg) is cfg
    with pytest.raises(ValueError):
        resolve_telemetry_config("loud")
    with pytest.raises(ValueError):
        TelemetryConfig(capacity=0)
    with pytest.raises(ValueError):
        TelemetryConfig(sample_every=0.0)


def test_emit_rejects_unknown_kind():
    bus = TelemetryBus(TelemetryConfig(enabled=True))
    with pytest.raises(ValueError):
        bus.emit("task_exploded")
    for kind in EVENT_KINDS:
        assert isinstance(kind, str)


def test_null_bus_is_noop_sink():
    bus = null_bus()
    assert not bus.enabled
    bus.emit("task_settled", task_id=0, worker=0, depth=1,
             fetch_seconds=0.0, exec_seconds=0.0)
    assert bus.events() == []
    # the aggregation path still runs (that is the single JobReport path)
    assert bus.metrics.snapshot()["counters"]["tasks_settled"] == 1


# -- metrics registry ---------------------------------------------------------


def test_quantile_exact_on_bucket_aligned_uniform():
    # values 1..100 over decade buckets: every bucket holds exactly 10,
    # so the interpolated estimate lands on the exact percentile
    m = MetricsRegistry()
    buckets = tuple(float(b) for b in range(10, 101, 10))
    for v in range(1, 101):
        m.observe("u", float(v), buckets=buckets)
    for q, exact in ((0.1, 10.0), (0.5, 50.0), (0.9, 90.0),
                     (0.95, 95.0), (0.99, 99.0), (1.0, 100.0)):
        assert m.quantile("u", q) == pytest.approx(exact)


def test_quantile_interpolates_within_bucket_width():
    # a known bimodal distribution: the estimate may only be off by the
    # interpolation error inside one bucket, never more
    m = MetricsRegistry()
    values = [0.5] * 50 + [5.0] * 50
    for v in values:
        m.observe("b", v, buckets=(1.0, 10.0))
    assert m.quantile("b", 0.25) == pytest.approx(0.5)
    exact_p75 = float(np.percentile(values, 75))
    est = m.quantile("b", 0.75)
    assert abs(est - exact_p75) <= 9.0            # ≤ one bucket width
    assert 1.0 <= est <= 10.0                     # inside the right bucket


def test_quantile_overflow_clamps_missing_none_bad_q_raises():
    m = MetricsRegistry()
    m.observe("h", 999.0, buckets=(1.0, 10.0))
    # overflow bucket clamps to the last finite bound — the estimator
    # never invents a value beyond the scale
    assert m.quantile("h", 0.99) == 10.0
    assert m.quantile("nope", 0.5) is None
    with pytest.raises(ValueError):
        m.quantile("h", 1.5)
    with pytest.raises(ValueError):
        m.quantile("h", -0.1)


def test_metrics_registry_counters_gauges_histograms():
    m = MetricsRegistry()
    m.inc("a")
    m.inc("a", 2.0)
    m.set_gauge("g", 7.5)
    for v in (0.5, 1.5, 99.0):
        m.observe("h", v, buckets=(1.0, 10.0))
    snap = m.snapshot()
    assert snap["counters"]["a"] == 3.0
    assert snap["gauges"]["g"] == 7.5
    h = snap["histograms"]["h"]
    assert h["buckets"] == [1.0, 10.0]
    assert h["counts"] == [1, 1, 1]       # ≤1, ≤10, overflow
    assert h["count"] == 3
    assert h["sum"] == pytest.approx(101.0)


# -- off-by-default: zero events AND bit-identical results --------------------


@pytest.mark.parametrize("backend", ["threaded", "simulated"])
def test_disabled_bus_records_nothing_results_identical(backend):
    samples, months = _dataset()
    # one worker: on real threads the depth at each settle counts the
    # tasks still ready, which only a fixed schedule repeats exactly
    p_off = Platform(_spec(backend=backend, n_workers=1))
    r_off = p_off.run(samples, months, WL)
    p_on = Platform(_spec(backend=backend, n_workers=1, telemetry=True))
    r_on = p_on.run(samples, months, WL)

    assert p_off.telemetry.events() == []
    assert not p_off.telemetry.enabled
    assert len(p_on.telemetry.events()) > 0
    assert _results_equal(r_off.result, r_on.result)
    # satellite: JobReport counters come from the one aggregation path
    # whether or not the ring records, so they must agree
    assert r_off.device_dispatches == r_on.device_dispatches
    assert r_off.bytes_uploaded == pytest.approx(r_on.bytes_uploaded)
    assert r_off.queue_depths == r_on.queue_depths


def test_depth_trace_populated_with_bus_disabled():
    # depth_trace is a bound sink fed by task_settled aggregation — it
    # must fill even when no event is recorded
    samples, months = _dataset()
    report = Platform(_spec()).run(samples, months, WL)
    assert report.queue_depths
    assert all(isinstance(d, int) for d in report.queue_depths)


# -- deterministic virtual-time event streams ---------------------------------


def _sim_events(seed):
    tasks = [sch.Task(i, (i,), 64.0) for i in range(12)]
    workers = [sch.SimWorker(w, speed=1.0 + 0.1 * w) for w in range(3)]
    params = sch.SimParams(exec_time=lambda t: 0.01 + t.task_id * 1e-3,
                           fetch_time=lambda t: 0.002)
    bus = TelemetryBus(TelemetryConfig(enabled=True), virtual=True)
    sch.simulate_job(tasks, workers, params,
                     sch.SchedulerConfig(seed=seed), telemetry=bus)
    return [(e.kind, e.ts, tuple(sorted(e.fields.items())))
            for e in bus.events()]


def test_sim_event_stream_identical_per_seed():
    a, b = _sim_events(5), _sim_events(5)
    assert a == b                       # kinds, order, virtual timestamps
    assert a != _sim_events(6)          # the stream tracks the schedule
    kinds = {k for k, _, _ in a}
    assert {"task_claimed", "task_settled"} <= kinds


def test_sim_platform_events_virtual_and_deterministic():
    samples, months = _dataset()

    def stream(run):
        p = Platform(_spec(backend="simulated", n_workers=4,
                           telemetry=True))
        p.run(samples, months, WL)
        return [(e.kind, e.ts) for e in p.telemetry.events()]

    a, b = stream(0), stream(1)
    # the cost MODEL is calibrated from fresh wall-clock measurements
    # each run, so virtual timestamps jitter at the µs level — but the
    # schedule (kinds + order) is fixed per seed, and settlement times
    # advance monotonically on the virtual clock
    assert [k for k, _ in a] == [k for k, _ in b]
    settles_a = [t for k, t in a if k == "task_settled"]
    assert settles_a == sorted(settles_a)


# -- bounded rings under chaos ------------------------------------------------


def test_ring_bounded_under_chaos_plan():
    samples, months = _dataset(n=24)
    plan = FaultPlan.from_seed(33, n_workers=2, n_nodes=4, n_tasks=24,
                               worker_crashes=1, node_kills=0,
                               latency_spikes=0)
    cfg = TelemetryConfig(enabled=True, capacity=16)
    spec = _spec(telemetry=cfg, lease_seconds=0.5)
    p = Platform(spec, fault_injector=FaultInjector(plan))
    baseline = Platform(_spec(lease_seconds=0.5)).run(samples, months, WL)
    chaotic = p.run(samples, months, WL)

    assert _results_equal(baseline.result, chaotic.result)
    assert len(p.telemetry.events()) <= 16          # ring bound holds
    snap = p.telemetry.snapshot()
    assert snap["events_recorded"] >= len(p.telemetry.events())
    assert snap["capacity"] == 16
    # the aggregate counters keep full totals even after ring eviction
    assert (snap["metrics"]["counters"]["tasks_settled"]
            >= baseline.n_tasks)


def test_fault_fired_events_recorded():
    samples, months = _dataset(n=24)
    plan = FaultPlan(events=(
        FaultEvent("worker_crash", target=0, at_claims=1),))
    p = Platform(_spec(telemetry=True, lease_seconds=0.5),
                 fault_injector=FaultInjector(plan))
    p.run(samples, months, WL)
    fired = p.telemetry.events("fault_fired")
    assert len(fired) == 1
    assert fired[0].fields["fault_kind"] == "worker_crash"


# -- trace export -------------------------------------------------------------

_VALID_PH = {"X", "B", "E", "i", "M", "s", "f"}


def test_trace_round_trips_with_valid_perfetto_fields(tmp_path):
    samples, months = _dataset()
    p = Platform(_spec(telemetry=True))
    report = p.run(samples, months, WL)
    path = os.path.join(tmp_path, "trace.json")
    write_trace(p.telemetry, path)
    with open(path) as fh:
        doc = json.loads(fh.read())
    evs = doc["traceEvents"]
    assert evs
    for ev in evs:
        assert ev["ph"] in _VALID_PH
        assert isinstance(ev["name"], str)
        if ev["ph"] == "X":
            assert ev["dur"] >= 0
            assert ev["ts"] >= 0
    # one span per executed task, phases monotone within each task
    execs = [e for e in evs if e["ph"] == "X"
             and e.get("cat") == "exec"]
    assert len(execs) == report.tasks_executed
    fetches = {e["name"].split(":")[0]: e for e in evs
               if e["ph"] == "X" and e.get("cat") == "fetch"}
    for e in execs:
        task = e["name"].split(":")[0]
        f = fetches.get(task)
        if f is not None:
            assert f["ts"] <= e["ts"]
            # ts/dur are rounded to 1e-3 µs independently, so the
            # boundary can land 0.002 µs past the exec start
            assert f["ts"] + f["dur"] <= e["ts"] + 0.01


def test_trace_wave_flow_events_link_tasks():
    samples, months = _dataset()
    p = Platform(_spec(telemetry=True))
    p.run(samples, months, WL)
    trace = build_trace(p.telemetry.events())["traceEvents"]
    starts = [e for e in trace if e["ph"] == "s"]
    finishes = [e for e in trace if e["ph"] == "f"]
    n_waves = len(p.telemetry.events("wave_dispatched"))
    assert len(starts) == n_waves > 0
    assert finishes                          # settlements bind the flow
    ids = {e["id"] for e in starts}
    assert all(e["id"] in ids for e in finishes)


# -- sampler + snapshot -------------------------------------------------------


def test_sampler_rows_and_failing_provider():
    bus = TelemetryBus(TelemetryConfig(enabled=True, sample_every=9.0))
    s = TelemetrySampler(bus)
    s.add_provider("good", lambda: {"depth": 3.0})

    def bad():
        raise RuntimeError("flaky gauge")

    s.add_provider("bad", bad)
    s.sample_once()
    rows = bus.samples()
    assert len(rows) == 1
    assert rows[0]["good.depth"] == 3.0
    assert not any(k.startswith("bad.") for k in rows[0])
    assert bus.metrics.snapshot()["gauges"]["good.depth"] == 3.0


def test_sampler_noop_when_disabled():
    bus = null_bus()
    s = TelemetrySampler(bus)
    s.add_provider("x", lambda: {"v": 1.0})
    s.start()
    assert not s.running
    s.sample_once()
    assert bus.samples() == []
    s.stop()


def test_service_snapshot_and_exports(tmp_path):
    samples, months = _dataset()
    spec = _spec(telemetry=True, n_workers=3)
    with PlatformService(spec) as svc:
        h = svc.register_dataset(samples, months)
        tickets = [svc.submit(h, WL, seed=s) for s in (1, 2, 3)]
        for t in tickets:
            t.result(timeout=300)
        snap = svc.telemetry_snapshot()
        trace = svc.write_trace(os.path.join(tmp_path, "svc.json"))
        svc.write_report(os.path.join(tmp_path, "svc.html"))
    assert snap["enabled"]
    assert snap["events_by_kind"]["job_done"] == 3
    assert snap["events_by_kind"]["job_admitted"] == 3
    assert snap["service"]["jobs_completed"] == 3
    settled = snap["events_by_kind"]["task_settled"]
    execs = [e for e in trace["traceEvents"]
             if e["ph"] == "X" and e.get("cat") == "exec"]
    assert len(execs) == settled > 0
    html = open(os.path.join(tmp_path, "svc.html")).read()
    assert html.lstrip().lower().startswith("<!doctype html")
    assert "tasks_settled" in html
    assert "src=" not in html and "href=" not in html   # self-contained


def test_service_disabled_bus_stays_empty():
    samples, months = _dataset()
    with PlatformService(_spec()) as svc:
        h = svc.register_dataset(samples, months)
        svc.submit(h, WL, seed=1).result(timeout=300)
        assert svc.telemetry.events() == []
        assert not svc.sampler.running
        # consolidated counters still flow into stats()
        assert svc.stats()["device_dispatches"] > 0


# -- report rendering ---------------------------------------------------------


def test_render_report_smoke():
    bus = TelemetryBus(TelemetryConfig(enabled=True))
    bus.emit("task_settled", task_id=0, worker=0, depth=2,
             fetch_seconds=0.001, exec_seconds=0.004)
    bus.record_sample({"queue": 2.0})
    html = render_report(bus, title="unit smoke")
    assert "unit smoke" in html
    assert "task_settled" in html
    json.dumps(html)                    # plain text, no stray bytes


def test_render_report_histogram_quantile_table():
    # the histograms section is a quantile summary (p50/p90/p95/p99),
    # not raw bucket dumps
    bus = TelemetryBus(TelemetryConfig(enabled=True))
    for v in (0.001, 0.002, 0.01, 0.05, 0.2):
        bus.metrics.observe("exec_seconds", v)
    html = render_report(bus, title="quantiles")
    assert "Histogram quantiles" in html
    for col in ("p50", "p90", "p95", "p99"):
        assert col in html


# -- build_trace edge cases ---------------------------------------------------


def _xspans(trace, cat=None):
    return [e for e in trace if e["ph"] == "X"
            and (cat is None or e.get("cat") == cat)]


def test_build_trace_zero_duration_spans():
    events = [
        Event(1, 1.0, "task_claimed", {"task_ids": (0,), "worker": 0}),
        Event(2, 1.0, "task_settled",
              {"task_id": 0, "worker": 0, "depth": 0,
               "fetch_seconds": 0.0, "exec_seconds": 0.0}),
    ]
    trace = build_trace(events)["traceEvents"]
    spans = _xspans(trace)
    assert spans                               # queue + task + exec
    for e in spans:
        assert e["dur"] == 0.0
        assert e["ts"] == pytest.approx(1.0 * 1e6)
    # a zero fetch_seconds settle emits no fetch span at all
    assert _xspans(trace, "fetch") == []


def test_build_trace_clamps_settle_before_claim():
    # clock skew between emit sites: the settle is stamped BEFORE its
    # claim, and the measured phases are longer than the window — every
    # span must clamp monotone against the claim, never go negative
    claim_us = 5.0 * 1e6
    events = [
        Event(1, 5.0, "task_claimed", {"task_ids": (0,), "worker": 0}),
        Event(2, 4.0, "task_settled",
              {"task_id": 0, "worker": 0, "depth": 0,
               "fetch_seconds": 2.0, "exec_seconds": 3.0}),
    ]
    trace = build_trace(events)["traceEvents"]
    for e in _xspans(trace):
        assert e["dur"] >= 0.0
    (queue,) = _xspans(trace, "queue")
    assert queue["ts"] == pytest.approx(claim_us)
    assert queue["dur"] == 0.0
    (fetch,) = _xspans(trace, "fetch")
    (exc,) = _xspans(trace, "exec")
    assert fetch["ts"] >= claim_us             # clamped to the claim
    assert exc["ts"] >= fetch["ts"]            # phases stay ordered
    assert exc["dur"] == 0.0


def test_build_trace_fused_wave_fans_out_per_job():
    # one fused wave over three jobs: job_ids aligned with task_ids, so
    # each member settles under its own job name and binds the SAME flow
    events = [
        Event(1, 1.0, "wave_dispatched",
              {"task_ids": (0, 1, 2), "job_ids": (7, 8, 9),
               "wave_size": 3, "nbytes": 3.0, "seconds": 0.25}),
        Event(2, 2.0, "task_settled",
              {"job_id": 7, "task_id": 0, "worker": 0, "depth": 2,
               "fetch_seconds": 0.0, "exec_seconds": 0.5}),
        Event(3, 2.5, "task_settled",
              {"job_id": 8, "task_id": 1, "worker": 1, "depth": 1,
               "fetch_seconds": 0.0, "exec_seconds": 0.5}),
        Event(4, 3.0, "task_settled",
              {"job_id": 9, "task_id": 2, "worker": 0, "depth": 0,
               "fetch_seconds": 0.0, "exec_seconds": 0.5}),
    ]
    trace = build_trace(events)["traceEvents"]
    (start,) = [e for e in trace if e["ph"] == "s"]
    finishes = [e for e in trace if e["ph"] == "f"]
    assert len(finishes) == 3
    assert all(e["id"] == start["id"] for e in finishes)
    names = {e["name"] for e in _xspans(trace, "exec")}
    assert names == {"j7/t0:exec", "j8/t1:exec", "j9/t2:exec"}


# -- sampler final flush ------------------------------------------------------


def test_sampler_stop_flushes_final_row_for_subtick_job():
    # a job shorter than one sample_every tick must still contribute at
    # least one time-series row: stop() flushes a final sample_once()
    bus = TelemetryBus(TelemetryConfig(enabled=True, sample_every=30.0))
    s = TelemetrySampler(bus)
    s.add_provider("svc", lambda: {"depth": 2.0})
    s.start()
    s.stop()                     # immediately: no tick ever fired
    rows = bus.samples()
    assert len(rows) == 1
    assert rows[0]["svc.depth"] == 2.0
    s.stop()                     # idempotent: no second row
    assert len(bus.samples()) == 1


# -- DESIGN.md §13.6 taxonomy table stays in sync -----------------------------


def test_event_kinds_table_matches_design_doc():
    import re
    path = os.path.join(os.path.dirname(__file__), "..", "DESIGN.md")
    with open(path) as fh:
        doc = fh.read()
    section = doc.split("### §13.6 EVENT_KINDS reference", 1)[1]
    section = section.split("\n### ", 1)[0].split("\n## ", 1)[0]
    documented = set(re.findall(r"^\| `([a-z_]+)` \|", section,
                                flags=re.MULTILINE))
    assert documented == set(EVENT_KINDS)


def test_span_names_table_matches_design_doc():
    import re
    path = os.path.join(os.path.dirname(__file__), "..", "DESIGN.md")
    with open(path) as fh:
        doc = fh.read()
    section = doc.split("### §13.7 SPAN_NAMES reference", 1)[1]
    section = section.split("\n### ", 1)[0].split("\n## ", 1)[0]
    documented = set(re.findall(r"^\| `([a-z_.]+)` \|", section,
                                flags=re.MULTILINE))
    assert documented == set(tel.SPAN_NAMES)


# -- host spans on the device trace (DESIGN.md §13.7) -------------------------


@pytest.mark.parametrize("make", [lambda: tel.span("platform.uploads"),
                                  lambda: null_bus().span("dispatch"),
                                  lambda: TelemetryBus(virtual=True).span(
                                      "platform.nope"),
                                  lambda: tel.SpanClock().span(
                                      "bench.window").__enter__()])
def test_span_rejects_unknown_name(make):
    with pytest.raises(ValueError, match="unknown telemetry span"):
        make()


def test_span_is_a_null_context_on_a_virtual_bus():
    import contextlib

    import jax

    assert isinstance(TelemetryBus(virtual=True).span("platform.upload"),
                      contextlib.nullcontext)
    assert isinstance(null_bus().span("platform.upload", job=1),
                      jax.profiler.TraceAnnotation)


def test_span_clock_sums_seconds_per_name():
    import time

    clock = tel.SpanClock(job=3)
    for _ in range(2):
        with clock.span("platform.upload"):
            time.sleep(0.01)
    with pytest.raises(RuntimeError):
        with clock.span("platform.dispatch"):
            raise RuntimeError("a failed dispatch still took its time")
    assert set(clock.seconds) == {"platform.upload", "platform.dispatch"}
    assert clock.seconds["platform.upload"] >= 0.02


PER_TASK_ALOD = SubsampleWorkload("alod", "alod", draws=30, draw_size=256,
                                  grid=16)


@pytest.mark.parametrize("workload", [PER_TASK_ALOD, WL],
                         ids=["per-task-jnp", "pallas-wave"])
def test_served_path_emits_every_span_on_the_trace(workload, tmp_path):
    """One threaded service job of each kind under ``jax.profiler`` on the
    CPU: its ``.xplane.pb`` holds every span name, and the job's ticket
    is charged the stages the benchmark reads."""
    import glob

    import jax

    from bench import program_spans

    samples, months = _dataset()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with PlatformService(_spec()) as svc:
            h = svc.register_dataset(samples, months)
            first = svc.submit(h, workload, seed=1)
            first.result(timeout=300)
            ticket = svc.submit(h, workload, seed=2)
            ticket.result(timeout=300)
    finally:
        jax.profiler.stop_trace()
    # one dispatch per task on the per-task path, fewer on the wave path
    assert ((ticket.device_dispatches == ticket.n_tasks)
            == (workload is PER_TASK_ALOD))
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {name for name, _, _ in program_spans.read_spans(path)}
    assert names == set(tel.SPAN_NAMES)
    for name in ("platform.upload", "platform.fetch_result",
                 "platform.reduce", "platform.finalize"):
        assert ticket.span_seconds[name] > 0, name

