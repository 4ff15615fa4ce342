"""The plain reference: a job's final statistics, recomputed from the data
set and the job's seed, by the semantics the service states.

It imports nothing of the program.  What it restates:

* tasks: the samples in key order, cut into runs of
  ``round(knee_bytes / mean sample bytes)``;
* a task's block: ``max_count`` rows (a short task repeats its samples,
  row ``r`` is sample ``r % k``), each row wrap-padded to a power of two:
  the longest row of the task, or of the whole data set for ``moments``;
  column ``c`` of a sample of length ``L`` is observation ``c % L``;
* draws: task ``t`` of a job with seed ``s`` draws its indices with
  threefry from ``PRNGKey(s + t)``, as ``jax.random.randint`` gives them;
* the statistic of the drawn observations, summed over every task, and
  the job's result from those sums.

Arithmetic is in ``dtype``: float64 for the reference, a lower precision
for the control (``bfloat16``: the values, every product and every sum).
Only the indices come from JAX (threefry, on the host's CPU device where
there is one).
"""

from __future__ import annotations

import functools
from typing import Dict, List

import numpy as np

EXACT = ("count", "n")          # outputs that must match exactly
NEVER = 1e30                    # the gap of an answer of the wrong shape


def pow2(n: int) -> int:
    return 1 << (max(int(n), 1) - 1).bit_length()


def task_groups(lengths: np.ndarray, knee_bytes: float,
                itemsize: int = 4) -> List[np.ndarray]:
    n = len(lengths)
    mean = max(1.0, float(np.mean(lengths * itemsize)))
    count = max(1, int(round(knee_bytes / mean)))
    return [np.arange(i, min(i + count, n)) for i in range(0, n, count)]


@functools.lru_cache(maxsize=None)
def _drawer(shape: tuple, maxval: int):
    import jax
    import jax.numpy as jnp

    def one(seed):
        return jax.random.randint(jax.random.PRNGKey(seed), shape, 0, maxval,
                                  dtype=jnp.int32)

    return jax.jit(jax.vmap(one))


def draw(seeds: np.ndarray, shape: tuple, maxval: int) -> np.ndarray:
    """``[len(seeds), *shape]`` threefry indices in ``[0, maxval)``."""
    import jax

    try:
        device = jax.devices("cpu")[0]
    except RuntimeError:
        device = jax.devices()[0]
    seeds = jax.device_put(np.asarray(seeds, np.int32), device)
    return np.asarray(_drawer(tuple(shape), int(maxval))(seeds))


def _scatter(acc: np.ndarray, cells: np.ndarray, vals) -> None:
    vals = np.broadcast_to(np.asarray(vals, acc.dtype), cells.shape)
    if acc.dtype == np.float64:
        acc += np.bincount(cells, weights=vals, minlength=len(acc))
    else:
        np.add.at(acc, cells, vals)


def _by_width(groups, widths):
    buckets: Dict[int, List[int]] = {}
    for t, w in enumerate(widths):
        buckets.setdefault(w, []).append(t)
    return buckets.items()


def _grid_stat(ds, groups, seed: int, wl: dict, dtype):
    """``monthly_mean`` and ``alod``: per-row draws along the row."""
    stat, grid = wl["statistic"], wl["grid"]
    draws, size = wl["draws"], wl["draw_size"]
    rows = max(len(g) for g in groups)
    widths = [pow2(ds.lengths[g].max()) for g in groups]
    a = np.zeros(grid, dtype)                # sum / sum_curve
    hits = np.zeros(grid, dtype)             # count / hits
    n = 0
    for width, tasks in _by_width(groups, widths):
        idx = draw(seed + np.asarray(tasks), (draws, rows, size), width)
        for t, tidx in zip(tasks, idx):
            g = groups[t]
            sample = g[np.arange(rows) % len(g)][None, :, None]
            flat = ds.offsets[sample] + tidx % ds.lengths[sample]
            x = ds.values[flat].astype(dtype)          # [draws, rows, size]
            if stat == "monthly_mean":
                cell = np.clip(ds.month_values[flat], 0, grid - 1).ravel()
                _scatter(a, cell, x.ravel())
                _scatter(hits, cell, 1.0)
                continue
            cell = np.clip(np.floor(tidx / width * grid).astype(np.int64),
                           0, grid - 1).ravel()
            mean = x.mean(axis=2, keepdims=True, dtype=dtype)
            sd = x.std(axis=2, keepdims=True, dtype=dtype) + dtype(1e-6)
            z = np.abs((x - mean) / sd)
            _scatter(a, cell, z.ravel())
            _scatter(hits, cell, 1.0)
            n += rows * draws
    if stat == "monthly_mean":
        return {"monthly_mean": a / np.maximum(hits, dtype(1.0)),
                "count": hits}
    return {"alod": a / np.maximum(hits, dtype(1.0)), "n": float(n)}


def _add_tiled(acc: np.ndarray, x: np.ndarray) -> None:
    """``acc[c] += x[c % len(x)]`` for every column ``c``."""
    n = len(x)
    whole = len(acc) // n * n
    acc[:whole].reshape(-1, n)[...] += x
    acc[whole:] += x[:len(acc) - whole]


def _moments(ds, groups, seed: int, wl: dict, dtype):
    """``moments``: whole padded rows drawn, summed column by column."""
    n_idx = wl["draws"] * wl["draw_size"]
    rows = max(len(g) for g in groups)
    width = pow2(ds.lengths.max())
    idx = draw(seed + np.arange(len(groups)), (n_idx,), rows)
    hits = np.zeros(len(ds.lengths), np.int64)
    for g, tidx in zip(groups, idx):
        np.add.at(hits, g[tidx % len(g)], 1)
    total = np.zeros(width, dtype)
    total_sq = np.zeros(width, dtype)
    for s in np.flatnonzero(hits):
        x = ds.values[ds.offsets[s]:ds.offsets[s] + ds.lengths[s]]
        x = x.astype(dtype)
        h = dtype(hits[s])
        _add_tiled(total, h * x)
        _add_tiled(total_sq, h * (x * x))
    count = len(groups) * n_idx
    mean = total / dtype(count)
    var = np.maximum(total_sq / dtype(count) - mean * mean, dtype(0.0))
    return {"mean": mean, "var": var, "count": float(count)}


def job_result(ds, knee_bytes: float, seed: int, wl: dict,
               dtype=np.float64) -> Dict[str, np.ndarray]:
    """The final statistics of the job with seed ``seed`` over ``ds``."""
    groups = task_groups(ds.lengths, knee_bytes, ds.values.itemsize)
    fn = _moments if wl["statistic"] == "moments" else _grid_stat
    return fn(ds, groups, seed, wl, dtype)


def gaps(got: Dict[str, np.ndarray], want: Dict[str, np.ndarray]
         ) -> Dict[str, float]:
    """Per output: the widest gap, relative to the reference's largest
    magnitude, or the absolute gap for the exact outputs (counts)."""
    out = {}
    for key, ref in want.items():
        ref = np.asarray(ref, np.float64)
        val = np.asarray(got[key], np.float64)
        if val.shape != ref.shape or not np.all(np.isfinite(val)):
            out[key] = NEVER
            continue
        gap = float(np.max(np.abs(val - ref))) if ref.size else 0.0
        if key not in EXACT:
            gap /= max(float(np.max(np.abs(ref))), 1e-30)
        out[key] = gap
    return out
