"""CPU rehearsal: every cell's control flow at a tiny size, through the
same harness a chip run uses (the look for a chip skipped).  It checks
control flow and correctness only; a CPU time is no measurement, and no
result line is printed."""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from conftest import CELLS, ROOT, tiny_overrides, tiny_run


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_and_matches_the_reference(cell):
    result = tiny_run(cell, seed=4_000_000_017 + len(cell))
    assert result["correct"], result["check"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert "setup_s" in result["metrics"]
    assert list(result)[-1] == "check"
    assert result["device"]["platform"] == "cpu"


@pytest.mark.parametrize("cell", CELLS[:2])
def test_traced_run_reports_per_layer_metrics(cell):
    result = tiny_run(cell, trace=True)
    assert result["correct"], result["check"]
    # the CPU has no device trace: only the host-side readers report
    assert {"tasks_per_dispatch", "upload_mb_per_job",
            "first_query_s"} <= set(result["metrics"])
    assert "setup_s" not in result["metrics"]


def test_a_run_without_a_chip_exits_non_zero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "3", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_a_directory_of_only_the_benchmark_files_exits_non_zero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "3", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_the_result_line_is_json_with_the_contracts_keys():
    result = tiny_run(CELLS[0])
    line = json.loads(json.dumps(result))
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        line["device"])
    for check in line["check"].values():
        assert set(check) == {"value", "limit"}


def test_poisson_schedule_is_one_set_of_gaps_in_each_seeds_order():
    from bench import traffic

    a = traffic.poisson_offsets(2.0, 30.0, 0, seed=1)
    b = traffic.poisson_offsets(2.0, 30.0, 0, seed=2 ** 33 + 5)
    assert len(a) == len(b) > 30
    assert a[-1] == pytest.approx(b[-1]) and a[-1] < 30.0
    assert sorted(a.tolist()) != sorted(b.tolist())
    assert sorted(np.diff(a, prepend=0.0)) == pytest.approx(
        sorted(np.diff(b, prepend=0.0)))


def test_open_loop_rehearsal_reports_generator_lateness():
    from bench import harness, traffic

    cell = harness.load_cell(ROOT, CELLS[0])
    s = harness.open_session(ROOT, CELLS[0], seed=9, t_start=0.0,
                             require_chip=False,
                             overrides=tiny_overrides(cell.config),
                             say=lambda _text: None)
    try:
        mix = dict(cell.traffic, arrivals="poisson", rate_hz=8.0,
                   schedule_seed=0)
        window = traffic.drive(s.service, s.handle, s.workload,
                               traffic.plan(mix, 1.0, 9), 1.0)
    finally:
        s.close()
    assert window.jobs and len(window.lateness) == len(window.jobs)
    assert all(j.result is not None for j in window.jobs)
