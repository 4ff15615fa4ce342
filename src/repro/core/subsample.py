"""Data-parallel subsampling statistics engine in JAX (thesis §3.1, Fig 1).

Samples are keyed blocks of observations (a *family's* SNP sequence for the
EAGLET workload; a *movie's* ratings for the Netflix workload).  A map task
takes a block of samples, draws ``draws`` random subsamples per sample, and
computes a statistic from each draw; reduce combines the per-task partials
into the job result (the ALOD curve / per-month rating means).

The random index gather is the cache-hostile access pattern the whole
thesis is about — task (block) size controls the working set it rampages
over.  Two TPU Pallas kernels compute the hot spot without gathering
element by element: ``repro.kernels.subsample_gather`` the row-subsample
``moments`` statistic, and ``repro.kernels.grid_stat`` ``monthly_mean``
(the same draws as :func:`map_task`).  This module is the jnp engine of
``alod`` and the oracle of both.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class SubsampleWorkload:
    name: str                 # "eaglet" | "netflix_high" | "netflix_low"
    statistic: str            # "alod" | "monthly_mean"
    draws: int                # subsamples per sample (EAGLET: 30)
    draw_size: int            # observations per subsample
    grid: int = 64            # output curve resolution (ALOD grid / months)


EAGLET = SubsampleWorkload("eaglet", "alod", draws=30, draw_size=256,
                           grid=64)
# High confidence: two orders of magnitude more ratings than low (§4.1.1.2)
NETFLIX_HIGH = SubsampleWorkload("netflix_high", "monthly_mean", draws=8,
                                 draw_size=2048, grid=120)
NETFLIX_LOW = SubsampleWorkload("netflix_low", "monthly_mean", draws=8,
                                draw_size=32, grid=120)

WORKLOADS = {w.name: w for w in (EAGLET, NETFLIX_HIGH, NETFLIX_LOW)}


# ---------------------------------------------------------------------------
# Map task (jitted, static block shape)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("draws", "draw_size", "grid",
                                             "statistic"))
def map_task(
    data: jax.Array,          # [n_samples, sample_len] float32
    months: jax.Array,        # [n_samples, sample_len] int32 (netflix) or 0s
    rng: jax.Array,
    *,
    draws: int,
    draw_size: int,
    grid: int,
    statistic: str,
) -> Dict[str, jax.Array]:
    """Subsample each sample ``draws`` times and compute the statistic.

    Returns partials suitable for tree reduction:
      alod:          {"sum_curve": [grid], "count": []}
      monthly_mean:  {"sum": [grid], "count": [grid]}
    """
    ns, sl = data.shape
    idx = jax.random.randint(rng, (draws, ns, draw_size), 0, sl)
    # the cache-hostile random gather (thesis Fig 2): draw-major order —
    # every draw sweeps the whole block's working set (all samples), so
    # blocks larger than cache evict between sweeps (the LRU/stack-
    # distance argument of §3.2)
    gathered = jnp.take_along_axis(
        data[None, :, :], idx, axis=2)               # [draws, ns, draw_size]
    gathered = jnp.swapaxes(gathered, 0, 1)          # [ns, draws, draw_size]
    idx = jnp.swapaxes(idx, 0, 1)

    if statistic == "alod":
        # EAGLET-like: per-draw windowed score curve over a common grid,
        # averaged over draws (the ALOD combination step).
        pos = idx.astype(jnp.float32) / sl            # marker positions [0,1)
        cell = jnp.clip((pos * grid).astype(jnp.int32), 0, grid - 1)
        # information score per observation: |z|-like evidence
        mean = jnp.mean(gathered, axis=2, keepdims=True)
        sd = jnp.std(gathered, axis=2, keepdims=True) + 1e-6
        z = jnp.abs((gathered - mean) / sd)
        curve = jnp.zeros((grid,), jnp.float32).at[cell.reshape(-1)].add(
            z.reshape(-1))
        hits = jnp.zeros((grid,), jnp.float32).at[cell.reshape(-1)].add(1.0)
        return {"sum_curve": curve, "hits": hits,
                "count": jnp.asarray(float(ns * draws))}

    # netflix monthly means: average subsampled ratings per month cell
    m = jnp.take_along_axis(months[:, None, :], idx, axis=2)
    m = jnp.clip(m, 0, grid - 1)
    sums = jnp.zeros((grid,), jnp.float32).at[m.reshape(-1)].add(
        gathered.reshape(-1))
    cnts = jnp.zeros((grid,), jnp.float32).at[m.reshape(-1)].add(1.0)
    return {"sum": sums, "count": cnts}


def reduce_stats(partials: Sequence[Dict[str, jax.Array]],
                 statistic: str) -> Dict[str, np.ndarray]:
    """Combine per-task partials (the reduce stage)."""
    acc = jax.tree.map(lambda *xs: sum(xs[1:], xs[0]), *partials)
    if statistic == "alod":
        curve = np.asarray(acc["sum_curve"]) / np.maximum(
            np.asarray(acc["hits"]), 1.0)
        return {"alod": curve, "n": float(acc["count"])}
    mean = np.asarray(acc["sum"]) / np.maximum(np.asarray(acc["count"]), 1.0)
    return {"monthly_mean": mean, "count": np.asarray(acc["count"])}


def run_map_task_np(data: np.ndarray, months: np.ndarray,
                    seed: int, wl: SubsampleWorkload):
    """Convenience wrapper binding a workload; returns numpy partials."""
    rng = jax.random.PRNGKey(seed)
    out = map_task(jnp.asarray(data), jnp.asarray(months), rng,
                   draws=wl.draws, draw_size=wl.draw_size, grid=wl.grid,
                   statistic=wl.statistic)
    return jax.tree.map(np.asarray, out)


# ---------------------------------------------------------------------------
# Exhaustive references (accuracy-vs-speed tradeoff measurements)
# ---------------------------------------------------------------------------


def exhaustive_monthly_mean(data: np.ndarray, months: np.ndarray,
                            grid: int) -> np.ndarray:
    sums = np.zeros(grid)
    cnts = np.zeros(grid)
    m = np.clip(months, 0, grid - 1)
    np.add.at(sums, m.reshape(-1), data.reshape(-1))
    np.add.at(cnts, m.reshape(-1), 1.0)
    return sums / np.maximum(cnts, 1.0)
