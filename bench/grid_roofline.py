"""The least work of a grid statistic (``monthly_mean``, ``alod``): what
``grid_stat_roofline`` sets against the device's busy time.

Like ``roofline.moments_least_work`` it does not depend on how the
program computes the statistic: the true bytes of the distinct
observations that each task's draws touch, counted once per task (a
rating and its month: 4 + 4 bytes), and two operations per drawn value
(its add into its bin's sum and into its bin's count).  The draws are
the reference's: ``reference.task_groups``, ``reference.draw`` per
bucket width, and column ``c`` of a sample of length ``L`` is its
observation ``c % L``.  A program that reads a repeated row or a padded
column once raises its share; no count of padding can push it past 100%.
"""

from __future__ import annotations

from typing import Iterable, Tuple

import numpy as np

from bench import reference

STATISTICS = ("monthly_mean", "alod")
GRID_FLOPS_PER_VALUE = 2


def grid_least_work(ds, knee_bytes: float, seeds: Iterable[int],
                    query: dict) -> Tuple[float, float]:
    """``(bytes, flops)`` that jobs with ``seeds`` need at the least."""
    groups = reference.task_groups(ds.lengths, knee_bytes,
                                   ds.values.itemsize)
    rows = max(len(g) for g in groups)
    shape = (query["draws"], rows, query["draw_size"])
    per_obs = ds.values.itemsize + (0 if ds.month_values is None
                                    else ds.month_values.itemsize)
    widths = [reference.pow2(ds.lengths[g].max()) for g in groups]
    samples = [g[np.arange(rows) % len(g)][None, :, None] for g in groups]
    distinct = drawn = 0
    for seed in seeds:
        for width, tasks in reference._by_width(groups, widths):
            idx = reference.draw(seed + np.asarray(tasks), shape, width)
            for t, tidx in zip(tasks, idx):
                s = samples[t]
                flat = ds.offsets[s] + tidx % ds.lengths[s]
                distinct += len(np.unique(flat))
                drawn += tidx.size
    return (float(distinct * per_obs),
            float(drawn * GRID_FLOPS_PER_VALUE))
