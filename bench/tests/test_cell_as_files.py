"""A cell added as files only: the harness finds a configuration, a
traffic mix and a cell by name, with no code edited."""

import json
import shutil

import pytest

from conftest import ROOT, tiny_overrides, tiny_run


def add_cell(tmp_path, cell: dict, config: dict = None) -> None:
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    if config is not None:
        bench["configs"].append(config)
    bench["workloads"].append(cell)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))


def test_new_cell_from_files_in_a_temporary_directory(tmp_path):
    config = json.loads(
        (ROOT / "bench" / "configs" / "eaglet-thesis.json").read_text())
    add_cell(tmp_path, {"name": "small-alod-three", "config": "eaglet-small",
                        "traffic": "alod-three-clients", "chips": 1,
                        "why": "a test"},
             {"name": "eaglet-small", "source": "a test",
              "file": "bench/configs/eaglet-small.json",
              "reduced": ["n_families"]})
    config.update(name="eaglet-small", n_families=24, mean_markers=200)
    (tmp_path / "bench" / "configs" / "eaglet-small.json").write_text(
        json.dumps(config))
    (tmp_path / "bench" / "traffic" / "alod-three-clients.json").write_text(
        json.dumps({"query": {"name": "eaglet", "statistic": "alod",
                              "draws": 30, "draw_size": 256, "grid": 64},
                    "arrivals": "closed", "clients": 3}))
    (tmp_path / "bench" / "workloads" / "small-alod-three.json"
     ).write_text(json.dumps({"sample_jobs": 2, "limits": {
         "alod": 1e-5, "n": 0}}))

    over = tiny_overrides(dict(config, name="eaglet-thesis"))
    over.pop("n_families")
    result = tiny_run("small-alod-three", root=tmp_path, overrides=over,
                      trace=True)
    assert result["correct"], result["check"]
    # per-layer metrics listed for other cells only are left out
    assert "tasks_per_dispatch" not in result["metrics"]


@pytest.mark.parametrize("cell,traffic", [
    ("netflix-monthly-closed", "monthly-closed"),
    ("netflix-moments-closed", "moments-closed")])
def test_staged_netflix_cells_run_from_their_files(tmp_path, cell, traffic):
    """The Netflix cells' files are in ``bench/`` and wait for their chip
    measurements (PERF.md); adding their entries is all they need."""
    config = json.loads(
        (ROOT / "bench" / "configs" / "netflix-prize.json").read_text())
    add_cell(tmp_path, {"name": cell, "config": "netflix-prize",
                        "traffic": traffic, "chips": 1, "why": "a test"},
             {"name": "netflix-prize", "source": "a test",
              "file": "bench/configs/netflix-prize.json", "reduced": []})
    result = tiny_run(cell, root=tmp_path, overrides=tiny_overrides(config))
    assert result["correct"], result["check"]
