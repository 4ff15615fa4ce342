"""The plain reference against a computation by hand."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import data, reference

# three samples of lengths 2, 3 and 1; 4-byte values; a knee of two
# samples' mean bytes makes tasks [0, 1] and [2] with 2 rows each
VALUES = np.asarray([1.0, 2.0, 10.0, 20.0, 30.0, 7.0], np.float32)
MONTHS = np.asarray([0, 1, 1, 0, 2, 2], np.int32)
LENGTHS = np.asarray([2, 3, 1])
KNEE = 2 * 4 * 2.0


def dataset():
    offsets = np.asarray([0, 2, 5])
    split = lambda a: dict(enumerate(np.split(a, [2, 5])))  # noqa: E731
    return data.Dataset(split(VALUES), split(MONTHS), VALUES, MONTHS,
                        LENGTHS, offsets)


def fixed_draws(monkeypatch, table):
    """``reference.draw`` returns ``table[maxval]`` for every seed."""
    def draw(seeds, shape, maxval):
        return np.stack([np.asarray(table[maxval]).reshape(shape)
                         for _ in seeds])
    monkeypatch.setattr(reference, "draw", draw)


def test_task_groups_repeat_the_papers_packing():
    groups = reference.task_groups(LENGTHS, KNEE)
    assert [g.tolist() for g in groups] == [[0, 1], [2]]


def test_draw_is_jax_randint_per_task_seed():
    got = reference.draw(np.asarray([5, 6]), (2, 3), 10)
    for i, seed in enumerate((5, 6)):
        want = jax.random.randint(jax.random.PRNGKey(seed), (2, 3), 0, 10,
                                  dtype=jnp.int32)
        np.testing.assert_array_equal(got[i], np.asarray(want))


def test_moments_by_hand(monkeypatch):
    # draws 1 x 3 rows of the 2-row task blocks: rows 0, 1, 1
    fixed_draws(monkeypatch, {2: [0, 1, 1]})
    wl = {"statistic": "moments", "draws": 1, "draw_size": 3, "grid": 0}
    out = reference.job_result(dataset(), KNEE, 0, wl)
    # every row is padded to pow2(3) = 4 columns, wrapping:
    # s0 = [1, 2, 1, 2], s1 = [10, 20, 30, 10], s2 = [7, 7, 7, 7]
    s0, s1, s2 = (np.asarray(r, float) for r in
                  ([1, 2, 1, 2], [10, 20, 30, 10], [7, 7, 7, 7]))
    # task 0 draws s0, s1, s1; task 1 (one sample, repeated) draws s2 x3
    rows = [s0, s1, s1, s2, s2, s2]
    mean = sum(rows) / 6
    var = sum(r * r for r in rows) / 6 - mean * mean
    np.testing.assert_allclose(out["mean"], mean, rtol=1e-15)
    np.testing.assert_allclose(out["var"], var, rtol=1e-12)
    assert out["count"] == 6


def test_monthly_mean_by_hand(monkeypatch):
    # one draw of two columns per row; task 0 rows are 4 wide (pow2(3)),
    # task 1's 1 wide
    fixed_draws(monkeypatch, {4: [[[0, 3], [2, 3]]], 1: [[[0, 0], [0, 0]]]})
    wl = {"statistic": "monthly_mean", "draws": 1, "draw_size": 2,
          "grid": 2}
    out = reference.job_result(dataset(), KNEE, 0, wl)
    # task 0, s0 columns 0, 3 -> (1, m0), (2, m1); s1 columns 2, 3 ->
    # (30, m2 clipped to 1), (10, m1); task 1: s2 four times (7, m2 -> 1)
    sums = np.asarray([1.0, 2 + 30 + 10 + 4 * 7])
    counts = np.asarray([1.0, 7.0])
    np.testing.assert_allclose(out["monthly_mean"], sums / counts)
    np.testing.assert_array_equal(out["count"], counts)


def test_alod_by_hand(monkeypatch):
    fixed_draws(monkeypatch, {4: [[[0, 3], [2, 3]]], 1: [[[0, 0], [0, 0]]]})
    wl = {"statistic": "alod", "draws": 1, "draw_size": 2, "grid": 2}
    out = reference.job_result(dataset(), KNEE, 0, wl)
    # z = |x - mean| / (std + 1e-6) of each row's draw of two values:
    # two distinct values give z = 1 each; s2's two sevens give z = 0
    eps = 1e-6
    # s0 draws (1, 2): std 0.5; s1 draws (30, 10): std 10
    z0 = 0.5 / (0.5 + eps)
    z1 = 10.0 / (10.0 + eps)
    # cells: column / width * 2 -> s0 columns 0, 3 -> cells 0, 1;
    # s1 columns 2, 3 -> cells 1, 1; task 1 width 1, column 0 -> cell 0
    curve = np.asarray([z0 + 0.0 + 0.0, z0 + z1 + z1])
    hits = np.asarray([1.0 + 4.0, 3.0])
    np.testing.assert_allclose(out["alod"], curve / hits, rtol=1e-12)
    assert out["n"] == 4                      # 2 rows x 1 draw x 2 tasks


@pytest.mark.parametrize("key,got,want,gap", [
    ("mean", [1.0, 2.0], [1.0, 4.0], 0.5),
    ("count", [3.0, 4.0], [3.0, 5.0], 1.0),
    ("mean", [1.0], [1.0, 2.0], reference.NEVER),
    ("mean", [np.nan, 1.0], [1.0, 1.0], reference.NEVER),
])
def test_gaps_relative_exact_and_malformed(key, got, want, gap):
    assert reference.gaps({key: np.asarray(got)},
                          {key: np.asarray(want)})[key] == gap
