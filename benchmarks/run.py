"""Benchmark harness — one module per thesis table/figure.

Prints ``name,us_per_call,derived`` CSV and writes machine-readable
``BENCH_platform.json`` (per-config makespans, dispatch counts, phase
timings, the per-task-vs-wave comparison) so the perf trajectory is
tracked across PRs.  Figure map:
  Fig 2      bench_kneepoint        task-size→cost curve + knees
  Fig 4/8/9  bench_task_sizing      BTS vs BLT vs BTT speedups
  Fig 5/6    bench_platform_overhead  startup + per-task overhead + wave
  Fig 10/11  bench_jobsize          BTS vs Hadoop-like across job sizes
  Fig 12/13  bench_elasticity       core scaling + SLO-bounded choice
  Fig 14/15  bench_hetero           heterogeneity + virtualization
  Fig 16     bench_reduce_sim       reduce-stage model
  (kernels)  bench_kernels          Pallas/oracle microbenchmarks
  (§10)      bench_approx           error-bounded early-stop frontier
  (§11)      bench_sharded          multi-device sharded wave scaling
  (§12)      bench_faults           seeded fault injection + recovery
  (§14)      bench_cache            worker-side block cache traffic cut

``--smoke`` runs the fast subset (platform_overhead + kernels, scaled
down) for CI; the harness FAILS (exit 2) when the wave engine's
dispatch-count reduction regresses below the acceptance threshold.
"""

from __future__ import annotations

import argparse
import inspect
import json
import os
import pathlib
import sys
import time

# the wave engine must cut device dispatches by at least this factor at
# tiny-task sizing (ISSUE 2 acceptance criterion)
MIN_DISPATCH_RATIO = 5.0
# repeat queries on a registered dataset must upload ~0 bytes: at most
# this fraction of the first query's arena pack (ISSUE 3 criterion)
MAX_REPEAT_BYTES_FRACTION = 0.01
# wall-clock comparisons are noisy on shared CI runners, so the service
# burst's p95 only FAILS the gate when it exceeds sequential by this
# factor (measured headroom is ~69x); svc >= seq but under the factor
# warns.  The deterministic dispatch-count gate is the primary criterion.
SERVICE_P95_TOLERANCE = 1.2
# with one data node degraded to 5x latency, the balanced scheduler must
# beat FIFO placement by at least this makespan factor, bit-identically
# (ISSUE 4 acceptance criterion; measured headroom ~3x)
MIN_BALANCE_RATIO = 2.0
# an error-bounded query at the gated epsilon must execute at least this
# many times fewer tasks than the full run, AND the full-run answer must
# lie inside the reported confidence band (ISSUE 5 acceptance criterion;
# measured headroom ~3-3.6x)
MIN_APPROX_TASK_RATIO = 2.0
# --compare: metrics may regress by at most this fraction vs the
# committed baseline, else exit 2.  Byte metrics additionally get a
# small absolute slack (near-zero baselines like the ~128 B repeat
# upload would otherwise fail on any jitter); dispatch counts get +1
# (wave draining is timing-dependent at the margin — BTT lands on 4 or
# 5 dispatches run to run — while a real fusion loss jumps to dozens)
COMPARE_TOLERANCE = 0.10
COMPARE_BYTES_ABS_SLACK = 512.0
COMPARE_COUNT_ABS_SLACK = 1.0
# approx stop points ride the CI trajectory, whose exact settlement index
# moves a task or two with measured per-task costs — wider slack than
# plain dispatch counts, still far below a real early-stop regression
# (which jumps to the full task count)
COMPARE_APPROX_TOLERANCE = 0.30
COMPARE_APPROX_ABS_SLACK = 4.0
# sharded wave execution (ISSUE 6): at 8 emulated devices the
# tasks-per-dispatch amortization vs the 1-device mesh must be at least
# this (it is exactly 8x by construction — fixed per-device width, fixed
# task count — so any slip below 3x means sharded dispatch stopped
# packing full per-device waves).  Wall-clock throughput scaling is NOT
# gated: the CI mesh emulates 8 devices on one CPU core, so lanes run
# serially and wall time is flat — see bench_sharded's docstring.
MIN_SHARD_RATIO = 3.0
# fault recovery (ISSUE 7): a run with one injected worker crash + one
# node kill must finish within this factor of the fault-free makespan.
# Wall-clock is otherwise never gated, but bounded recovery IS the
# acceptance criterion here — the absolute slack keeps the gate stable
# when the fault-free denominator is a fraction of a second on CI
MAX_FAULT_MAKESPAN_RATIO = 1.5
FAULT_MAKESPAN_ABS_SLACK = 0.05
# unified telemetry (ISSUE 8): the enabled bus may cost at most this
# factor of the disabled run's makespan (median over interleaved pairs;
# the absolute slack keeps the gate stable when the denominator is a
# fraction of a second on CI), and on/off must be bit-identical
MAX_TELEMETRY_OVERHEAD = 1.05
TELEMETRY_OVERHEAD_ABS_SLACK = 0.05
# worker-side block cache (ISSUE 9): repeat/overlap query traffic to the
# data nodes must be cut by at least this factor with the cache on,
# bit-identically (measured headroom ≈ the 8-run/8-job arm size), and a
# zero-capacity cache must match the cacheless platform exactly
MIN_CACHE_FETCH_RATIO = 5.0
# SLO monitor (ISSUE 10): the enabled monitor may cost at most this
# factor of the monitor-off run's makespan (median over interleaved
# pairs, same slack convention as telemetry), the seeded-fault diagnosis
# must name every injected fault with zero findings on clean runs, and
# the critical-path phase seconds must reconstruct the job makespan
# within this tolerance on both backends
MAX_MONITOR_OVERHEAD = 1.05
MONITOR_OVERHEAD_ABS_SLACK = 0.05
CRITICAL_PATH_TOLERANCE = 0.05
SMOKE_MODULES = ("platform_overhead", "kernels", "service", "balance",
                 "approx", "sharded", "faults", "telemetry", "cache",
                 "monitor")


def _check_wave_regression(structured: dict) -> list:
    """Dispatch-count regression gate over bench_platform_overhead's
    structured wave results."""
    failures = []
    for plat, res in structured.get("wave", {}).items():
        ratio = res["dispatch_ratio"]
        if ratio < MIN_DISPATCH_RATIO:
            failures.append(
                f"wave dispatch ratio regressed on {plat}: {ratio:.2f}x "
                f"< {MIN_DISPATCH_RATIO}x "
                f"({res['per_task']['device_dispatches']} per-task vs "
                f"{res['wave']['device_dispatches']} wave dispatches)")
        if res["wave"]["makespan_s"] >= res["per_task"]["makespan_s"]:
            # recorded for trend analysis; wall time is noisy on shared
            # CI runners so it warns rather than fails
            print(f"# WARNING: wave not faster on {plat}: "
                  f"{res['wave']['makespan_s']:.3f}s vs "
                  f"{res['per_task']['makespan_s']:.3f}s", file=sys.stderr)
    return failures


def _check_service_regression(structured: dict) -> list:
    """ISSUE 3 gates over bench_service's structured results: repeat
    queries on a registered dataset must hit the cached arena (~0 bytes
    uploaded), and a burst of concurrent jobs through the service must
    use fewer total device dispatches than the same jobs run sequentially
    through one-shot Platform.run.  The p95 latency comparison is
    wall-clock and therefore tolerance-gated (warn below
    ``SERVICE_P95_TOLERANCE``x, fail above it)."""
    failures = []
    rep = structured.get("repeat")
    if rep:
        budget = max(MAX_REPEAT_BYTES_FRACTION * rep["first_bytes"], 4096.0)
        if rep["repeat_bytes_max"] > budget:
            failures.append(
                f"repeat-query upload not ~0 on registered dataset: "
                f"{rep['repeat_bytes_max']:.0f} bytes > {budget:.0f} "
                f"(first query uploaded {rep['first_bytes']:.0f})")
    conc = structured.get("concurrent")
    if conc:
        seq, svc = conc["sequential"], conc["service"]
        if svc["p95_s"] >= SERVICE_P95_TOLERANCE * seq["p95_s"]:
            failures.append(
                f"service concurrent p95 regressed vs sequential "
                f"Platform.run: {svc['p95_s']:.3f}s >= "
                f"{SERVICE_P95_TOLERANCE}x {seq['p95_s']:.3f}s")
        elif svc["p95_s"] >= seq["p95_s"]:
            print(f"# WARNING: service burst p95 not below sequential: "
                  f"{svc['p95_s']:.3f}s vs {seq['p95_s']:.3f}s (within "
                  f"{SERVICE_P95_TOLERANCE}x tolerance)", file=sys.stderr)
        if svc["dispatches"] >= seq["dispatches"]:
            failures.append(
                f"service burst used no fewer dispatches than sequential "
                f"runs: {svc['dispatches']} >= {seq['dispatches']}")
    return failures


def _check_approx_regression(structured: dict) -> list:
    """ISSUE 5 gates over bench_approx's structured results: at the
    gated epsilon the early stop must cut executed tasks ≥2× with the
    full-run answer inside the reported confidence band, and the burst's
    cancelled capacity must observably serve the peer jobs (fewer total
    tasks + dispatches, peers bit-identical)."""
    failures = []
    for wl, res in structured.get("frontier", {}).items():
        gate = res.get("gate")
        if not gate:
            continue
        if not gate["stopped"]:
            failures.append(
                f"approx {wl}: early stop never fired at the gated "
                f"epsilon {gate['epsilon']:.4g}")
        if gate["task_ratio"] < MIN_APPROX_TASK_RATIO:
            failures.append(
                f"approx {wl}: only {gate['task_ratio']:.2f}x fewer "
                f"tasks at gated epsilon (need ≥ "
                f"{MIN_APPROX_TASK_RATIO}x; "
                f"{gate['tasks_executed']}/{res['n_tasks']} executed)")
        if not gate["covered"]:
            failures.append(
                f"approx {wl}: full-run answer escaped the reported "
                f"confidence band (half_width {gate['half_width']:.4g}, "
                f"max_abs_err {gate['max_abs_err']:.4g})")
    cap = structured.get("capacity")
    if cap:
        if cap["eps_cancelled"] <= 0:
            failures.append("approx capacity: error-bounded burst job "
                            "cancelled no tasks")
        we, ae = cap["with_eps"], cap["all_exact"]
        if we["tasks_executed_total"] >= ae["tasks_executed_total"]:
            failures.append(
                f"approx capacity: burst with early stop executed no "
                f"fewer tasks ({we['tasks_executed_total']} >= "
                f"{ae['tasks_executed_total']})")
        if we["dispatches"] >= ae["dispatches"]:
            failures.append(
                f"approx capacity: burst with early stop used no fewer "
                f"dispatches ({we['dispatches']} >= {ae['dispatches']})")
        if not cap["peers_bit_identical"]:
            failures.append("approx capacity: peer jobs' results "
                            "diverged from the all-exact burst")
    return failures


def _check_sharded_regression(structured: dict) -> list:
    """ISSUE 6 gates over bench_sharded's structured results: every mesh
    size bit-identical to the single-device run, and (when the full
    1→8 emulated sweep ran) the deterministic tasks-per-dispatch
    amortization at the top mesh ≥ MIN_SHARD_RATIO.  Wall-clock
    tasks/second is a warn-only trend (one-core emulation)."""
    failures = []
    sc = structured.get("scaling")
    if not sc:
        return failures
    for mesh, res in sorted(sc["meshes"].items(), key=lambda kv: int(kv[0])):
        if not res["bit_identical"]:
            failures.append(
                f"sharded wave at mesh={mesh} diverged from the "
                f"single-device result on keys {res['diverged_keys']}")
    if not sc["gate_active"]:
        print(f"# WARNING: sharded scaling gate skipped: only "
              f"{sc['devices_available']} device(s); run under XLA_FLAGS="
              f"--xla_force_host_platform_device_count=8 to gate the "
              f"1-to-8 sweep", file=sys.stderr)
        return failures
    ratio = sc["dispatch_amortization"]
    if ratio < MIN_SHARD_RATIO:
        top = sc["meshes"][str(sc["max_mesh"])]
        failures.append(
            f"sharded dispatch amortization regressed: {ratio:.2f}x at "
            f"mesh={sc['max_mesh']} (need >= {MIN_SHARD_RATIO}x; "
            f"{top['device_dispatches']} dispatches for "
            f"{sc['n_tasks']} tasks)")
    tps1 = sc["meshes"]["1"]["tasks_per_second"]
    tps_top = sc["meshes"][str(sc["max_mesh"])]["tasks_per_second"]
    if tps_top < tps1:
        print(f"# WARNING: sharded wall-clock throughput not above "
              f"1-device at mesh={sc['max_mesh']}: {tps_top:.0f} vs "
              f"{tps1:.0f} tasks/s (expected on the one-core emulated "
              f"mesh; trend only)", file=sys.stderr)
    return failures


def _check_faults_regression(structured: dict) -> list:
    """ISSUE 7 gates over bench_faults' structured results: injected
    worker-crash + node-kill runs bit-identical on both execution paths
    with bounded recovery makespan; checkpoint-interrupted jobs resume
    executing ONLY the missing tasks, bit-identically; every seeded
    chaos plan reproduces the clean result."""
    failures = []
    for path, res in structured.get("kill", {}).items():
        if not res["bit_identical"]:
            failures.append(
                f"faults kill/{path}: result diverged from the "
                f"fault-free run under injected worker crash + node "
                f"kill")
        if res["events_fired"] < res["events_planned"]:
            failures.append(
                f"faults kill/{path}: only {res['events_fired']} of "
                f"{res['events_planned']} planned faults fired — the "
                f"scenario did not exercise recovery")
        limit = (MAX_FAULT_MAKESPAN_RATIO * res["makespan_clean_s"]
                 + FAULT_MAKESPAN_ABS_SLACK)
        if res["makespan_faulty_s"] > limit:
            failures.append(
                f"faults kill/{path}: recovery makespan "
                f"{res['makespan_faulty_s']:.3f}s > "
                f"{MAX_FAULT_MAKESPAN_RATIO}x fault-free "
                f"{res['makespan_clean_s']:.3f}s (+ "
                f"{FAULT_MAKESPAN_ABS_SLACK}s slack)")
    for path, res in structured.get("resume", {}).items():
        if not res["interrupted"]:
            failures.append(
                f"faults resume/{path}: injected checkpoint crash did "
                f"not interrupt the job")
        if res["restored"] <= 0:
            failures.append(
                f"faults resume/{path}: checkpoint restored no "
                f"partials")
        if not res["only_missing"]:
            failures.append(
                f"faults resume/{path}: resume did not execute exactly "
                f"the missing tasks ({res['executed_new']} executed, "
                f"{res['restored']} restored, {res['n_tasks']} total)")
        if not res["bit_identical"]:
            failures.append(
                f"faults resume/{path}: resumed result diverged from "
                f"the uninterrupted run")
    chaos = structured.get("chaos")
    if chaos and not chaos["all_bit_identical"]:
        bad = [s for s, r in chaos["seeds"].items()
               if not r["bit_identical"]]
        failures.append(
            f"faults chaos: seeds {bad} diverged from the clean run")
    return failures


def _check_telemetry_regression(structured: dict) -> list:
    """ISSUE 8 gates over bench_telemetry's structured results: the
    enabled bus stays within the overhead budget with results
    bit-identical to telemetry-off on both backends (disabled records
    exactly zero events), the exported trace carries ≥1 exec span per
    executed task with monotone phase timestamps, and chaos runs keep
    the ring bound."""
    failures = []
    ov = structured.get("overhead")
    if ov:
        limit = (MAX_TELEMETRY_OVERHEAD
                 + TELEMETRY_OVERHEAD_ABS_SLACK
                 / max(ov["median_off_s"], 1e-9))
        if ov["median_ratio"] > limit:
            failures.append(
                f"telemetry overhead: enabled median makespan "
                f"{ov['median_on_s']:.3f}s is {ov['median_ratio']:.3f}x "
                f"disabled ({ov['median_off_s']:.3f}s) > "
                f"{MAX_TELEMETRY_OVERHEAD}x budget (+ "
                f"{TELEMETRY_OVERHEAD_ABS_SLACK}s slack)")
        if not ov["bit_identical"]:
            failures.append("telemetry overhead: an on/off pair's "
                            "results diverged")
    for backend, res in structured.get("identity", {}).items():
        if not res["bit_identical"]:
            failures.append(
                f"telemetry identity/{backend}: result with telemetry "
                f"on diverged from telemetry off")
        if res["disabled_events"] != 0:
            failures.append(
                f"telemetry identity/{backend}: disabled bus recorded "
                f"{res['disabled_events']} events (must be 0)")
        if res["enabled_events"] <= 0:
            failures.append(
                f"telemetry identity/{backend}: enabled bus recorded "
                f"no events")
    tr = structured.get("trace")
    if tr:
        if not tr["spans_per_task_ok"]:
            failures.append(
                f"telemetry trace: {tr['exec_spans']} exec spans for "
                f"{tr['tasks_settled']} settled tasks (need one span "
                f"per executed task)")
        if not tr["monotone_ok"]:
            failures.append("telemetry trace: fetch/exec phase "
                            "timestamps not monotone within a task")
    chaos = structured.get("chaos")
    if chaos:
        if not chaos["all_bounded"]:
            bad = [s for s, r in chaos["seeds"].items()
                   if not r["ring_bounded"]]
            failures.append(
                f"telemetry chaos: ring bound {chaos['capacity']} "
                f"violated on seeds {bad}")
        if not chaos["all_bit_identical"]:
            bad = [s for s, r in chaos["seeds"].items()
                   if not r["bit_identical"]]
            failures.append(
                f"telemetry chaos: seeds {bad} diverged from the clean "
                f"run with telemetry enabled")
    return failures


def _check_monitor_regression(structured: dict) -> list:
    """ISSUE 10 gates over bench_monitor's structured results: the
    enabled monitor stays within the overhead budget bit-identically,
    the disabled default leaves no taps/alerts and matches monitor-on
    results exactly, every injected fault is named in diagnose() output
    while clean runs stay finding-free, and the critical-path phase sum
    reconstructs the makespan on both backends."""
    failures = []
    ov = structured.get("overhead")
    if ov:
        limit = (MAX_MONITOR_OVERHEAD
                 + MONITOR_OVERHEAD_ABS_SLACK
                 / max(ov["median_off_s"], 1e-9))
        if ov["median_ratio"] > limit:
            failures.append(
                f"monitor overhead: enabled median makespan "
                f"{ov['median_on_s']:.3f}s is {ov['median_ratio']:.3f}x "
                f"monitor-off ({ov['median_off_s']:.3f}s) > "
                f"{MAX_MONITOR_OVERHEAD}x budget (+ "
                f"{MONITOR_OVERHEAD_ABS_SLACK}s slack)")
        if not ov["bit_identical"]:
            failures.append("monitor overhead: an off/on pair's results "
                            "diverged — the monitor leaked into the "
                            "statistic")
    dis = structured.get("disabled")
    if dis:
        if not dis["monitor_absent"]:
            failures.append("monitor disabled: default MonitorOptions "
                            "still constructed a monitor")
        if dis["taps"] != 0:
            failures.append(
                f"monitor disabled: {dis['taps']} tap(s) left on the "
                f"telemetry bus (must be 0)")
        if dis["alert_events"] != 0:
            failures.append(
                f"monitor disabled: {dis['alert_events']} alert "
                f"event(s) emitted (must be 0)")
        if not dis["bit_identical"]:
            failures.append("monitor disabled: monitor-off result "
                            "diverged from monitor-on")
    diag = structured.get("diagnosis")
    if diag:
        if not diag["all_clean_zero"]:
            bad = {s: c for s, c in diag["clean_seeds"].items() if c}
            failures.append(
                f"monitor diagnosis: false positives on clean seeds "
                f"{bad} (every clean run must diagnose zero findings)")
        fa = diag["fault"]
        if fa["fired"] != fa["planned"]:
            failures.append(
                f"monitor diagnosis: only {fa['fired']} of "
                f"{fa['planned']} planned faults fired")
        if not fa["all_named"]:
            missed = [k for k, ok in fa["named"].items() if not ok]
            failures.append(
                f"monitor diagnosis: injected faults not named in "
                f"diagnose() output: {missed}")
        if not fa["bit_identical"]:
            failures.append("monitor diagnosis: seeded-fault result "
                            "diverged from the clean run")
    for backend, res in structured.get("critical_path", {}).items():
        if abs(res["median_ratio"] - 1.0) > CRITICAL_PATH_TOLERANCE:
            failures.append(
                f"monitor critical_path/{backend}: phase sum is "
                f"{res['median_ratio']:.3f}x the measured makespan "
                f"(must be within {CRITICAL_PATH_TOLERANCE:.0%})")
    return failures


def _check_cache_regression(structured: dict) -> list:
    """ISSUE 9 gates over bench_cache's structured results: repeat and
    overlapping queries must cut data-node fetch traffic ≥
    MIN_CACHE_FETCH_RATIO× with bit-identical results, and the
    zero-capacity cache must be indistinguishable from no cache."""
    failures = []
    for section in ("repeat", "overlap"):
        res = structured.get(section)
        if not res:
            continue
        if res["ratio"] < MIN_CACHE_FETCH_RATIO:
            failures.append(
                f"cache {section}: fetch traffic only cut "
                f"{res['ratio']:.2f}x ({res['off_fetches']} off vs "
                f"{res['on_fetches']} on; need >= "
                f"{MIN_CACHE_FETCH_RATIO}x)")
        if not res["bit_identical"]:
            failures.append(
                f"cache {section}: cached results diverged from the "
                f"uncached runs — the cache leaked into the statistic")
    dis = structured.get("disabled")
    if dis:
        if not dis["fetches_match"]:
            failures.append(
                f"cache disabled: zero-capacity cache changed fetch "
                f"traffic ({dis['zero_capacity_fetches']} vs "
                f"{dis['no_cache_fetches']} without a cache)")
        if not dis["bit_identical"]:
            failures.append(
                "cache disabled: zero-capacity results diverged from "
                "the cacheless platform")
    return failures


def _check_balance_regression(structured: dict) -> list:
    """ISSUE 4 gates over bench_balance's structured results."""
    failures = []
    deg = structured.get("degraded")
    if deg:
        if deg["ratio"] < MIN_BALANCE_RATIO:
            failures.append(
                f"balanced scheduling under a 5x-degraded data node only "
                f"{deg['ratio']:.2f}x better than FIFO placement "
                f"(need >= {MIN_BALANCE_RATIO}x)")
        if not deg["bit_identical"]:
            failures.append(
                "degraded-node run result diverged from the undegraded "
                "run — the data path leaked into the statistic")
    fo = structured.get("failover")
    if fo and not (fo["result_ok"] and fo["node0_down"]):
        failures.append(
            f"data-node failover broken: result_ok={fo['result_ok']} "
            f"node0_down={fo['node0_down']}")
    return failures


# metric extraction for the --compare regression gate: metric name ->
# (value, direction); "lower" metrics fail when they grow past the
# tolerance, "higher" metrics when they shrink past it.  Only
# deterministic counters (dispatch counts, bytes) and policy ratios are
# compared — wall-clock seconds are never gated here.
def _comparable_metrics(report: dict) -> dict:
    out = {}
    mods = report.get("modules", {})
    wave = (mods.get("platform_overhead", {})
            .get("structured", {}).get("wave", {}))
    for plat, res in wave.items():
        out[f"wave.{plat}.dispatches"] = (
            float(res["wave"]["device_dispatches"]), "lower")
        out[f"wave.{plat}.bytes_uploaded"] = (
            float(res["wave"]["bytes_uploaded"]), "lower")
        # dispatch_ratio is NOT compared: it is the same 4-vs-5 wave
        # jitter as the count, and the absolute MIN_DISPATCH_RATIO gate
        # already bounds it
    svc = mods.get("service", {}).get("structured", {})
    if svc.get("repeat"):
        out["service.repeat_bytes_max"] = (
            float(svc["repeat"]["repeat_bytes_max"]), "lower")
    if svc.get("concurrent"):
        out["service.burst_dispatches"] = (
            float(svc["concurrent"]["service"]["dispatches"]), "lower")
    approx = mods.get("approx", {}).get("structured", {})
    for wl, res in approx.get("frontier", {}).items():
        gate = res.get("gate")
        if gate:
            out[f"approx.{wl}.tasks_executed"] = (
                float(gate["tasks_executed"]), "lower")
    if approx.get("capacity"):
        out["approx.burst_tasks_executed"] = (
            float(approx["capacity"]["with_eps"]["tasks_executed_total"]),
            "lower")
    # sharded scaling: dispatch counts and tasks-per-dispatch are exact
    # (n_workers=1 FIFO waves over a fixed task count) so they get the
    # standard count tolerance; tasks_per_second is wall-clock and is
    # NOT compared.  A single-device run produces only the mesh-1 keys,
    # so baselines recorded under the 8-device mesh show the higher-mesh
    # keys as "skipped" rows there (by design, not a failure).
    sh = (mods.get("sharded", {}).get("structured", {})
          .get("scaling", {}))
    for mesh, res in sh.get("meshes", {}).items():
        out[f"sharded.mesh{mesh}.dispatches"] = (
            float(res["device_dispatches"]), "lower")
        out[f"sharded.mesh{mesh}.tasks_per_dispatch"] = (
            float(res["tasks_per_dispatch"]), "higher")
    if sh.get("gate_active"):
        out["sharded.dispatch_amortization"] = (
            float(sh["dispatch_amortization"]), "higher")
    # fault recovery: event counts and restore/re-execution counts are
    # deterministic (seeded plans, fixed checkpoint cadence); the
    # makespan ratio is wall-clock and gated by its own absolute check
    fa = mods.get("faults", {}).get("structured", {})
    for path, res in fa.get("kill", {}).items():
        out[f"faults.kill.{path}.events_fired"] = (
            float(res["events_fired"]), "higher")
    for path, res in fa.get("resume", {}).items():
        out[f"faults.resume.{path}.tasks_restored"] = (
            float(res["restored"]), "higher")
        out[f"faults.resume.{path}.executed_new"] = (
            float(res["executed_new"]), "lower")
    # telemetry: the burst trace's span count equals settled tasks (a
    # fixed 3-job burst with no early stop ⇒ deterministic); the
    # overhead ratio is wall-clock and gated by its own absolute check
    te = mods.get("telemetry", {}).get("structured", {})
    if te.get("trace"):
        out["telemetry.exec_spans"] = (
            float(te["trace"]["exec_spans"]), "higher")
    # block cache: cached-arm fetch counts carry prefetch claim-race
    # jitter (a few duplicate fetches during the fill run), so they get
    # the wider approx-style slack; the traffic-cut ratio is gated
    # absolutely by MIN_CACHE_FETCH_RATIO and (like the balance ratio)
    # is not compared here
    ca = mods.get("cache", {}).get("structured", {})
    for section in ("repeat", "overlap"):
        if ca.get(section):
            out[f"cache.{section}.on_fetches"] = (
                float(ca[section]["on_fetches"]), "lower")
    # bench_balance's makespan ratio is wall-clock-derived, so it is
    # gated by its own MIN_BALANCE_RATIO check, not compared here
    return out


def _compare_to_baseline(report: dict, baseline_path: str) -> list:
    """Exit-2 regression gate vs the committed BENCH_platform.json:
    compare shared deterministic metrics within COMPARE_TOLERANCE and
    write a markdown table to $GITHUB_STEP_SUMMARY (when set) and
    stdout."""
    with open(baseline_path) as fh:
        baseline = json.load(fh)
    cur = _comparable_metrics(report)
    base = _comparable_metrics(baseline)
    failures = []
    lines = ["## Benchmark comparison vs baseline", "",
             "| metric | baseline | current | Δ | status |",
             "|---|---:|---:|---:|---|"]
    for key in sorted(set(cur) & set(base)):
        c, direction = cur[key]
        b, _ = base[key]
        delta = (c - b) / b if b else 0.0
        if direction == "lower":
            if key.startswith(("approx.", "cache.")):
                tol, slack = (COMPARE_APPROX_TOLERANCE,
                              COMPARE_APPROX_ABS_SLACK)
            elif "bytes" in key:
                tol, slack = COMPARE_TOLERANCE, COMPARE_BYTES_ABS_SLACK
            else:
                tol, slack = COMPARE_TOLERANCE, COMPARE_COUNT_ABS_SLACK
            bad = c > max(b * (1.0 + tol), b + slack)
        else:
            bad = c < b * (1.0 - COMPARE_TOLERANCE)
        status = "❌ regressed" if bad else "✅ ok"
        if bad:
            failures.append(
                f"{key} regressed vs baseline: {c:.2f} vs {b:.2f} "
                f"({direction} is better, tolerance "
                f"{COMPARE_TOLERANCE:.0%})")
        lines.append(f"| {key} | {b:.2f} | {c:.2f} | {delta:+.1%} "
                     f"| {status} |")
    for key in sorted(set(base) - set(cur)):
        lines.append(f"| {key} | {base[key][0]:.2f} | — | — | skipped |")
    table = "\n".join(lines) + "\n"
    print(table)
    summary = os.environ.get("GITHUB_STEP_SUMMARY")
    if summary:
        with open(summary, "a") as fh:
            fh.write(table)
    return failures


_STRUCTURED_CHECKS = {
    "service": _check_service_regression,
    "balance": _check_balance_regression,
    "cache": _check_cache_regression,
    "platform_overhead": _check_wave_regression,
    "approx": _check_approx_regression,
    "sharded": _check_sharded_regression,
    "faults": _check_faults_regression,
    "telemetry": _check_telemetry_regression,
    "monitor": _check_monitor_regression,
}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("only", nargs="?", default=None,
                        help="run a single benchmark module by name")
    parser.add_argument("--smoke", action="store_true",
                        help="fast CI subset with scaled-down sizes")
    parser.add_argument("--json", default=None,
                        help="machine-readable output path ('' disables; "
                        "defaults to BENCH_platform.json on full and "
                        "--smoke runs — the smoke subset IS the committed "
                        "cross-PR record and the CI artifact — and off "
                        "for single-module runs so a partial report "
                        "never clobbers it)")
    parser.add_argument("--compare", default=None, metavar="BASELINE",
                        help="exit 2 when deterministic metrics (dispatch "
                        "counts, bytes uploaded, policy ratios) regress "
                        "beyond tolerance vs this committed "
                        "BENCH_platform.json; writes a markdown table to "
                        "$GITHUB_STEP_SUMMARY when set")
    parser.add_argument("--chaos", action="store_true",
                        help="add bench_balance's fault-injection pass "
                        "(random data-node slowdowns/kills; nightly CI)")
    args = parser.parse_args(argv)
    if args.json is None:
        args.json = "" if args.only else "BENCH_platform.json"

    from repro.platform.compile_cache import enable_compile_cache
    enable_compile_cache(pathlib.Path(__file__).resolve().parents[1]
                         / ".jax_cache")

    from benchmarks import (bench_approx, bench_balance, bench_cache,
                            bench_elasticity, bench_faults, bench_hetero,
                            bench_jobsize, bench_kernels, bench_kneepoint,
                            bench_monitor, bench_platform_overhead,
                            bench_reduce_sim, bench_service,
                            bench_sharded, bench_task_sizing,
                            bench_telemetry)
    modules = [
        # balance first: its FIFO-vs-balanced wall-clock ratio is the
        # noise-sensitive gate, and the JAX modules leave threadpools
        # behind that load the process
        ("balance", bench_balance),
        ("kneepoint", bench_kneepoint),
        ("task_sizing", bench_task_sizing),
        ("platform_overhead", bench_platform_overhead),
        ("jobsize", bench_jobsize),
        ("elasticity", bench_elasticity),
        ("hetero", bench_hetero),
        ("reduce_sim", bench_reduce_sim),
        ("kernels", bench_kernels),
        ("service", bench_service),
        ("approx", bench_approx),
        ("sharded", bench_sharded),
        ("faults", bench_faults),
        ("telemetry", bench_telemetry),
        ("cache", bench_cache),
        ("monitor", bench_monitor),
    ]

    report = {"schema": 1, "smoke": args.smoke, "modules": {}}
    failures = []
    print("name,us_per_call,derived")
    for name, mod in modules:
        if args.only and args.only != name:
            continue
        if args.smoke and name not in SMOKE_MODULES:
            continue
        params = inspect.signature(mod.run).parameters
        kwargs = {}
        if args.smoke and "smoke" in params:
            kwargs["smoke"] = True
        if args.chaos and "chaos" in params:
            kwargs["chaos"] = True
        t0 = time.perf_counter()
        rows = mod.run(**kwargs)
        took = time.perf_counter() - t0
        for row_name, us, derived in rows:
            print(f"{row_name},{us:.3f},{derived}")
        print(f"_meta.{name}.bench_seconds,{took * 1e6:.0f},wall")
        entry = {"bench_seconds": took,
                 "rows": [{"name": n, "us_per_call": us, "derived": d}
                          for n, us, d in rows]}
        structured = getattr(mod, "STRUCTURED", None)
        if structured:
            entry["structured"] = structured
            check = _STRUCTURED_CHECKS.get(name, _check_wave_regression)
            failures.extend(check(structured))
        report["modules"][name] = entry

    # compare BEFORE writing: when --compare and --json point at the
    # same path (a local `--smoke --compare BENCH_platform.json`), the
    # write must not clobber the baseline into a vacuous self-compare
    if args.compare:
        failures.extend(_compare_to_baseline(report, args.compare))

    if args.json:
        with open(args.json, "w") as fh:
            json.dump(report, fh, indent=2, sort_keys=True)
        print(f"# wrote {args.json}", file=sys.stderr)

    for msg in failures:
        print(f"# FAIL: {msg}", file=sys.stderr)
    return 2 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
