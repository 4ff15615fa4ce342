"""Shared helpers for the benchmark's own tests (``python -m pytest
bench/tests``).  They run on the CPU at tiny sizes: the data sets below
keep every configuration's shapes and only shrink its scale."""

import json
import os
import pathlib

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench import data, harness  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELLS = tuple(c["name"] for c in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["workloads"])
SAMPLES_PER_TASK = 16


def _tiny_knots(name: str) -> dict:
    """A knot-shaped configuration at 96 movies: every knot's count over
    three (which keeps the fewest at one rating), and the total that the
    scaled curve gives at that many movies."""
    config = json.loads(
        (ROOT / "bench" / "configs" / f"{name}.json").read_text())
    knots = [[q, count / 3] for q, count in config["size_quantiles"]]
    curve = data.knot_curve(knots, 96,
                            np.random.default_rng(config["size_seed"]))
    return dict(n_movies=96, size_quantiles=knots,
                n_ratings=int(round(curve.sum())))


TINY = {"netflix-prize": dict(n_movies=96, mean_ratings=48, min_ratings=8),
        "eaglet-thesis": dict(n_families=40, mean_markers=256),
        "netflix-prize-published": _tiny_knots("netflix-prize-published")}


def tiny_overrides(config: dict) -> dict:
    """Shrink a configuration and pin its knee to 16 samples per task."""
    over = dict(TINY[config["name"]])
    sizes = data.sample_sizes(dict(config, **over))
    over["knee_bytes"] = SAMPLES_PER_TASK * float(np.mean(sizes * 4))
    return over


def tiny_run(name: str, *, root=ROOT, seed: int = 20260101,
             seconds: float = 1.0, trace: bool = False, overrides=None):
    """One rehearsal run of a cell on the CPU; returns the result."""
    if overrides is None:
        overrides = tiny_overrides(harness.load_cell(root, name).config)
    return harness.run_cell(root, name, seed=seed, seconds=seconds,
                            trace=trace, t_start=0.0, require_chip=False,
                            overrides=overrides, say=lambda _text: None)


@pytest.fixture
def root():
    return ROOT
