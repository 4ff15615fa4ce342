"""The data sets that ``bench/data.py`` makes: the two configurations in
use draw exactly what they drew before the published-shape mode came,
and the staged ``netflix-prize-published`` draws the published set's
shape."""

import hashlib
import json

import numpy as np
import pytest

from bench import data, reference
from conftest import ROOT, TINY, tiny_run
from test_cell_as_files import add_cell

SEED = 2718281829


def digest(a: np.ndarray) -> str:
    return hashlib.sha256(a.tobytes()).hexdigest()


def config(name: str) -> dict:
    return json.loads(
        (ROOT / "bench" / "configs" / f"{name}.json").read_text())


# sizes at full scale, values and months at the tiny scale of conftest, at
# SEED, as the generator drew them before size_quantiles and month_weights
@pytest.mark.parametrize("name,sizes,values,months", [
    ("eaglet-thesis",
     "5d51f6f464364f878633de9f9e4f95e00936047d3cf0888d56afc559e07cd80c",
     "1151f5e7d44c268821e461144a796313a459becb9783f6ff54cca1591d865360",
     None),
    ("netflix-prize",
     "d6b417264900a1e543ef063236a8b8b6f426763397093600c970e90bacc6c79f",
     "1a4fab6b3a14e36f5e3bb4827b1b027c110b61a307ef81d5668fd0bead0cfd0d",
     "444226818bf96b4e3da39e882e331dc38f170f9614efb593a63ad76adabe9a57"),
])
def test_configs_in_use_draw_what_they_drew(name, sizes, values, months):
    cfg = config(name)
    assert digest(data.sample_sizes(cfg)) == sizes
    ds = data.generate(dict(cfg, **TINY[name]), SEED)
    assert digest(ds.values) == values
    got = None if ds.month_values is None else digest(ds.month_values)
    assert got == months


def test_published_shape():
    cfg = config("netflix-prize-published")
    sizes = data.sample_sizes(cfg)
    assert len(sizes) == 17770
    assert int(sizes.sum()) == 100480507
    assert sizes.min() == 3 and sizes.max() == 232944
    assert abs(float(np.median(sizes)) - 561) <= 1
    assert len(reference.task_groups(sizes, cfg["knee_bytes"])) == 278
    # movies come in an order unrelated to their popularity
    assert np.argmax(sizes) not in (0, len(sizes) - 1)
    assert abs(np.corrcoef(np.arange(len(sizes)), sizes)[0, 1]) < 0.05


def test_published_shape_does_not_depend_on_seed():
    cfg = dict(config("netflix-prize-published"),
               **TINY["netflix-prize-published"])
    a, b = data.generate(cfg, 1), data.generate(cfg, SEED)
    np.testing.assert_array_equal(a.lengths, b.lengths)
    assert not np.array_equal(a.values, b.values)


def test_published_months_follow_their_weights():
    cfg = dict(config("netflix-prize-published"),
               **TINY["netflix-prize-published"])
    months = data.generate(cfg, SEED).month_values
    assert months.min() >= 0 and months.max() < 74
    weights = np.asarray(cfg["month_weights"], np.float64)
    want = weights / weights.sum()
    got = np.bincount(months, minlength=74) / len(months)
    # within five binomial standard errors of each month's weight
    tolerance = 5 * np.sqrt(want * (1 - want) / len(months))
    assert np.all(np.abs(got - want) <= tolerance), np.abs(got - want)


def test_knots_far_from_the_total_are_refused():
    cfg = dict(config("netflix-prize-published"), n_ratings=17770 * 100)
    with pytest.raises(ValueError, match="size_quantiles"):
        data.sample_sizes(cfg)


def test_published_config_rehearses_as_a_cell(tmp_path):
    """The staged configuration with the ``monthly-closed`` traffic, added
    as files only, runs correct at the tiny scale."""
    cell = "netflix-published-monthly-closed"
    add_cell(tmp_path, {"name": cell, "config": "netflix-prize-published",
                        "traffic": "monthly-closed", "chips": 1,
                        "why": "a test"},
             {"name": "netflix-prize-published", "source": "a test",
              "file": "bench/configs/netflix-prize-published.json",
              "reduced": []})
    (tmp_path / "bench" / "workloads" / f"{cell}.json").write_text(
        (ROOT / "bench" / "workloads" / "netflix-monthly-closed.json")
        .read_text())
    result = tiny_run(cell, root=tmp_path)
    assert result["correct"], result["check"]
    assert result["attempted"] >= 1
