"""Data sets made from a configuration file and ``--seed``.

The sizes of the samples come from the configuration's own ``size_seed``,
so every run of a cell has the same set of shapes: the same task blocks,
the same compiled programs and the same amount of work.  ``--seed`` draws
the values.  Generation is in bulk: one array of all observations, cut
into per-sample views.

A ``netflix`` configuration draws its shape one of two ways.  By default
ratings per movie are lognormal(0, ``size_sigma``) times ``mean_ratings``
with a floor of ``min_ratings``, and months are spread evenly over
``n_months``.  Where it gives ``size_quantiles`` (``[q, ratings]`` knots,
``q`` from 0 to 1, the first knot the fewest ratings and the last the
most), each movie's count is that curve, log-linear in the count between
knots, read at one of ``n_movies`` evenly spaced levels, the levels in an
order drawn from ``size_seed``; the counts strictly between the two ends
are then scaled by one factor and rounded so that they total exactly
``n_ratings``.  Where it gives ``month_weights`` (one per month), months
are drawn with those weights.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import numpy as np


@dataclasses.dataclass
class Dataset:
    """Samples keyed ``0..n-1`` (``months`` is ``None`` where the data set
    has no time axis), with the flat arrays the reference reads."""

    samples: Dict[int, np.ndarray]
    months: Optional[Dict[int, np.ndarray]]
    values: np.ndarray            # all observations, sample after sample
    month_values: Optional[np.ndarray]
    lengths: np.ndarray           # observations per sample
    offsets: np.ndarray           # start of each sample in ``values``

    @property
    def nbytes(self) -> int:
        """True (unpadded) bytes of the observations a statistic reads."""
        return int(self.values.nbytes)


def sample_sizes(cfg: dict) -> np.ndarray:
    """The configuration's fixed sample sizes (independent of ``--seed``)."""
    rng = np.random.default_rng(cfg["size_seed"])
    kind = cfg["generator"]
    if kind == "netflix" and "size_quantiles" in cfg:
        return _quantile_sizes(cfg, rng)
    if kind == "netflix":
        n = rng.lognormal(0.0, cfg["size_sigma"], cfg["n_movies"])
        return np.maximum(cfg["min_ratings"],
                          (n * cfg["mean_ratings"]).astype(np.int64))
    if kind == "eaglet":
        n = rng.lognormal(0.0, cfg["size_sigma"], cfg["n_families"])
        sizes = np.maximum(cfg["min_markers"],
                           (n * cfg["mean_markers"]).astype(np.int64))
        for i, factor in enumerate(cfg["outliers"]):
            sizes[i] = int(factor * cfg["mean_markers"])
        return sizes
    raise ValueError(f"unknown generator {kind!r}")


def knot_curve(knots, n: int, rng: np.random.Generator) -> np.ndarray:
    """The knots' curve, log-linear in the count, read at ``n`` evenly
    spaced levels from 0 to 1 in an order drawn from ``rng``."""
    q, count = np.asarray(knots, np.float64).T
    level = rng.permutation(n) / (n - 1)
    return np.exp(np.interp(level, q, np.log(count)))


def _quantile_sizes(cfg: dict, rng: np.random.Generator) -> np.ndarray:
    knots, total = cfg["size_quantiles"], cfg["n_ratings"]
    fewest, most = knots[0][1], knots[-1][1]
    x = knot_curve(knots, cfg["n_movies"], rng)
    low = x == x.min()
    inner = ~(low | (x == x.max()))
    sizes = np.where(low, fewest, most).astype(np.int64)
    x[inner] *= (total - sizes[~inner].sum()) / x[inner].sum()
    sizes[inner] = np.floor(x[inner])
    # largest remainders first, ties in movie order
    frac = np.where(inner, x - np.floor(x), -1.0)
    short = total - int(sizes.sum())
    sizes[np.argsort(-frac, kind="stable")[:short]] += 1
    if sizes.min() < fewest or sizes.max() > most:
        raise ValueError(
            f"size_quantiles' mean is too far from n_ratings / n_movies: "
            f"counts run from {sizes.min()} to {sizes.max()}, outside "
            f"[{fewest}, {most}]")
    return sizes


def _months(cfg: dict, rng: np.random.Generator, total: int) -> np.ndarray:
    if "month_weights" not in cfg:
        return rng.integers(0, cfg["n_months"], total, dtype=np.int32)
    w = np.asarray(cfg["month_weights"], np.float64)
    if len(w) != cfg["n_months"]:
        raise ValueError(f"{len(w)} month_weights for {cfg['n_months']} "
                         f"months")
    cdf = (np.cumsum(w) / w.sum()).astype(np.float32)
    u = rng.random(total, dtype=np.float32)
    return np.searchsorted(cdf, u, side="right").astype(np.int32)


def _split(flat: np.ndarray, lengths: np.ndarray) -> Dict[int, np.ndarray]:
    return dict(enumerate(np.split(flat, np.cumsum(lengths)[:-1])))


def generate(cfg: dict, seed: int) -> Dataset:
    lengths = sample_sizes(cfg)
    offsets = np.concatenate([[0], np.cumsum(lengths)[:-1]])
    rng = np.random.default_rng(seed)
    n_samples, total = len(lengths), int(lengths.sum())
    if cfg["generator"] == "netflix":
        # rating = movie quality + drift over the months + noise, in [1, 5]
        quality = rng.uniform(2.0, 4.5, n_samples).astype(np.float32)
        trend = rng.uniform(-0.5, 0.5, n_samples).astype(np.float32)
        months = _months(cfg, rng, total)
        values = rng.standard_normal(total, dtype=np.float32)
        drift = months.astype(np.float32)
        drift *= np.float32(1.0 / cfg["n_months"])
        drift *= np.repeat(trend, lengths)
        values += drift
        del drift
        values += np.repeat(quality, lengths)
        np.clip(values, 1.0, 5.0, out=values)
        return Dataset(_split(values, lengths), _split(months, lengths),
                       values, months, lengths, offsets)
    # EAGLET: SNP-like markers, with a "disease locus" bump in a third of
    # the families (thesis 4.1.1.1)
    values = rng.standard_normal(total, dtype=np.float32)
    for fid in range(0, n_samples, 3):
        n = int(lengths[fid])
        locus = int(0.6 * n)
        lo, hi = max(0, locus - n // 20), min(n, locus + n // 20)
        values[offsets[fid] + lo:offsets[fid] + hi] += np.float32(1.5)
    return Dataset(_split(values, lengths), None, values, None, lengths,
                   offsets)
