"""The least work a statistic needs, and the share of the roofline that
a kernel's device time reaches.

The least work does not depend on how a kernel does it: the true
(unpadded) bytes of the distinct samples that each task's draws touch,
counted once per task, and the statistic's operations on those values.
A kernel that skips padding or reads a drawn sample once then raises its
share, and no count of padded or repeated rows can push a share past
100%.
"""

from __future__ import annotations

import json
import pathlib
from typing import Iterable, Tuple

import numpy as np

from bench import reference

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"
# moments: one add for the sum, one multiply and one add for the sum of
# squares, per value read
MOMENTS_FLOPS_PER_VALUE = 3


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def moments_least_work(lengths: np.ndarray, itemsize: int,
                       knee_bytes: float, seeds: Iterable[int],
                       n_idx: int) -> Tuple[float, float]:
    """``(bytes, flops)`` that jobs with ``seeds`` need at the least."""
    groups = reference.task_groups(lengths, knee_bytes, itemsize)
    rows = max(len(g) for g in groups)
    values = 0
    for seed in seeds:
        idx = reference.draw(seed + np.arange(len(groups)), (n_idx,), rows)
        for g, tidx in zip(groups, idx):
            values += int(lengths[np.unique(g[tidx % len(g)])].sum())
    return float(values * itemsize), float(values * MOMENTS_FLOPS_PER_VALUE)


def least_seconds(nbytes: float, flops: float, peak: dict
                  ) -> Tuple[float, str]:
    """The least time on the chip, and which peak bounds it."""
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    t_flop = flops / peak["bf16_flops_per_s"]
    return (t_mem, "hbm") if t_mem >= t_flop else (t_flop, "flops")
