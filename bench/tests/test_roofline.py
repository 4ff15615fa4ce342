"""The least work of ``moments_kernel_roofline``, counted by hand."""

import numpy as np
import pytest

from bench import reference, roofline


def test_least_work_counts_distinct_true_samples_once_per_task(monkeypatch):
    # samples of 3, 5, 2 and 7 values; knee of two samples: tasks [0, 1]
    # and [2, 3]; each task draws rows 0, 0, 1, 3 of its 2-row block
    # (row 3 is row 1 repeated): task 0 touches samples 0 and 1, task 1
    # samples 2 and 3
    lengths = np.asarray([3, 5, 2, 7])
    knee = 2 * float(np.mean(lengths * 4))
    monkeypatch.setattr(reference, "draw", lambda seeds, shape, maxval:
                        np.tile(np.asarray([0, 0, 1, 3]), (len(seeds), 1)))
    nbytes, flops = roofline.moments_least_work(lengths, 4, knee, [11, 12], 4)
    values = 2 * (3 + 5 + 2 + 7)          # two jobs
    assert nbytes == values * 4
    assert flops == values * roofline.MOMENTS_FLOPS_PER_VALUE


def test_a_sample_drawn_in_one_task_only_counts_there(monkeypatch):
    lengths = np.asarray([3, 5, 2, 7])
    knee = 2 * float(np.mean(lengths * 4))
    monkeypatch.setattr(reference, "draw", lambda seeds, shape, maxval:
                        np.zeros((len(seeds), 4), np.int32))
    nbytes, _ = roofline.moments_least_work(lengths, 4, knee, [5], 4)
    assert nbytes == (3 + 2) * 4          # row 0 of each task only


def test_least_seconds_names_its_bound():
    peak = {"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e12}
    assert roofline.least_seconds(2e9, 1e9, peak) == (2.0, "hbm")
    assert roofline.least_seconds(1e6, 5e12, peak) == (5.0, "flops")


def test_peaks_are_keyed_by_device_kind():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")
