"""Grid-statistic kernel (Pallas, TPU target): the ``monthly_mean`` map
task of a whole wave of tasks.

A task's block holds ``N`` rows (a movie's ratings, with each rating's
month) wrap-padded to a common width.  Every row draws ``D`` random
columns, and the task's partial is, per month bin ``b`` of ``grid``, the
sum of the drawn ratings whose month is ``b`` and their count; months
outside ``[0, grid)`` are clipped to its ends (the semantics of
``repro.core.subsample.map_task``).

``grid_stat_wave`` takes the ratings and the months in the lane layout
``[S, N, L, 128]`` of :mod:`repro.kernels.subsample_gather`, the wave's
arena slots as a prefetched scalar vector, and each task's draw indices.
Each grid step reads one group of a task's rows straight out of
``data[slot]`` and ``months[slot]`` (the block index follows the
prefetched slot, so the arena is read in place and Pallas' pipeline
double-buffers the reads across steps).  No element is gathered one by
one: for each chunk of 128 draws of a row,

* one MXU product ``U = row @ onehot(idx % 128)ᵀ`` (``[L, 128] @
  [128, 128]``) moves each draw's lane of every lane row into the
  draw's column, and a masked sum over the ``L`` lane rows keeps lane
  row ``idx // 128``: the drawn rating and month, in a ``[1, 128]``
  vector with one draw per lane;
* both steps are exact: the one-hot operand is 0 or 1, each column of
  ``U`` has one non-zero product, the float32 ratings enter the bfloat16
  MXU as three bfloat16 terms whose float32 sum is the rating, and a
  clipped month (an integer below 256, or two base-256 digits) is one
  term or two;
* the drawn ratings and ones are binned on the VPU into per-lane sums
  ``[grid, 128]`` that stay resident in the output block across the
  task's steps; the task's last step folds the lanes.

Every task is computed by itself, in the same order whatever wave it
rides in, so a wave is bit-identical to the same tasks run as waves of
one.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.subsample_gather import LANES, SUBLANES

ROWS_PER_STEP = 8            # rows of a task read per grid step, at most
RATING_TERMS = 3             # bfloat16 terms that sum to a float32 rating
# VMEM for one step's rating and month rows; the pipeline holds two
# steps' worth, which keeps a step well inside v5e's 16 MiB default
# scoped VMEM
ROW_BUFFER_BYTES = 4 << 20
# lane rows of one row a step takes at most: bounds the float32 [L, 128]
# temporaries of a chunk of draws
MAX_BLOCK = 1024


def _largest_divisor(n: int, cap: int) -> int:
    return max(d for d in range(1, min(n, cap) + 1) if n % d == 0)


def row_blocking(rows: int, lane_rows: int):
    """``(rows per step, lane rows per column block)``: whole rows, up
    to :data:`ROWS_PER_STEP` of them (a divisor of ``rows``) within
    :data:`ROW_BUFFER_BYTES`, when a row has at most :data:`MAX_BLOCK`
    lane rows; else one row a step, split into column blocks."""
    if lane_rows > MAX_BLOCK:
        return 1, _largest_divisor(lane_rows, MAX_BLOCK)
    fit = ROW_BUFFER_BYTES // (2 * LANES * 4 * lane_rows)
    return _largest_divisor(rows, min(ROWS_PER_STEP, fit)), lane_rows


def month_terms(grid: int) -> int:
    """bfloat16 terms that carry a clipped month exactly: one below 256,
    else two base-256 digits."""
    assert 0 < grid <= 1 << 16, grid
    return 1 if grid <= 256 else 2


def _grid_kernel(slots_ref, idx_ref, data_ref, months_ref, out_ref,
                 terms_ref, *, rows: int, block: int, n_draws: int,
                 grid: int):
    del slots_ref                                   # used by the index maps
    g = pl.program_id(1)                            # row group
    c = pl.program_id(2)                            # column block
    m_terms = terms_ref.shape[0] - RATING_TERMS
    bins = out_ref.shape[2]
    chunks = idx_ref.shape[2]

    @pl.when((g == 0) & (c == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    lane_of = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
    lane_row = jax.lax.broadcasted_iota(jnp.int32, (block, LANES), 0)
    month_bin = jax.lax.broadcasted_iota(jnp.int32, (bins, LANES), 0)

    def row(r, carry):
        x = data_ref[0, r]                          # [block, 128] float32
        for t in range(RATING_TERMS):
            term = x.astype(jnp.bfloat16)
            terms_ref[t] = term
            x = x - term.astype(jnp.float32)
        m = jnp.clip(months_ref[0, r], 0, grid - 1)
        for t in range(m_terms):
            digit = (m >> (8 * (m_terms - 1 - t))) & 255
            terms_ref[RATING_TERMS + t] = digit.astype(
                jnp.float32).astype(jnp.bfloat16)

        def chunk(k, carry):
            idx = idx_ref[0, r, pl.ds(k, 1), :]     # [1, 128]: 128 draws
            hi = (idx >> 7) - c * block             # lane row in the block
            pick = (lane_of == (idx & (LANES - 1))).astype(jnp.bfloat16)
            keep = lane_row == hi                   # [block, 128]

            def lane_pick(t):                       # [block, 128]
                return jnp.dot(terms_ref[t], pick,
                               preferred_element_type=jnp.float32)

            def kept(u):                            # lane row ``hi``
                return jnp.sum(jnp.where(keep, u, 0.0), axis=0,
                               keepdims=True)        # [1, 128]

            u = lane_pick(0)
            for t in range(1, RATING_TERMS):
                u = u + lane_pick(t)
            value = kept(u)
            u = lane_pick(RATING_TERMS)
            for t in range(RATING_TERMS + 1, RATING_TERMS + m_terms):
                u = u * 256.0 + lane_pick(t)        # next base-256 digit
            month = kept(u)
            live = ((hi >= 0) & (hi < block)
                    & (k * LANES + lanes < n_draws))
            hit = (month_bin == month.astype(jnp.int32)) & live
            out_ref[0, 0] += jnp.where(hit, value, 0.0)
            out_ref[0, 1] += hit.astype(jnp.float32)
            return carry

        return jax.lax.fori_loop(0, chunks, chunk, carry)

    jax.lax.fori_loop(0, rows, row, 0)

    @pl.when((g == pl.num_programs(1) - 1) & (c == pl.num_programs(2) - 1))
    def _fold():
        for s in range(2):
            total = jnp.sum(out_ref[0, s], axis=1, keepdims=True)
            out_ref[0, s] = jnp.broadcast_to(total, (bins, LANES))


def grid_stat_wave(
    data: jax.Array,          # [S, N, L, 128] float32 ratings, lane layout
    months: jax.Array,        # [S, N, L, 128] int32 months, lane layout
    slots: jax.Array,         # [B] int32: the block of each task in the wave
    indices: jax.Array,       # [B, N, D] int32 drawn columns of each row
    *,
    grid: int,
    interpret: bool,
) -> jax.Array:
    """Per-task ``monthly_mean`` partials of a wave: ``[B, 2, grid]`` =
    (sum of the drawn ratings, count of draws) per month bin, for the
    draws ``indices[b, n]`` along row ``n`` of block ``slots[b]``.  The
    indices must lie in ``[0, L·128)``; ``D`` is padded to whole chunks
    of 128 draws internally (the padding is masked)."""
    _s, n, lane_rows, lanes = data.shape
    assert lanes == LANES and months.shape == data.shape, (
        data.shape, months.shape)
    bsz, n_idx, n_draws = indices.shape
    assert n_idx == n and slots.shape == (bsz,), (indices.shape, slots.shape)
    d_pad = -(-n_draws // LANES) * LANES
    if d_pad != n_draws:
        indices = jnp.pad(indices, ((0, 0), (0, 0), (0, d_pad - n_draws)))
    indices = indices.reshape(bsz, n, d_pad // LANES, LANES)
    rows, block = row_blocking(n, lane_rows)
    bins = -(-grid // SUBLANES) * SUBLANES
    kernel = functools.partial(_grid_kernel, rows=rows, block=block,
                               n_draws=n_draws, grid=grid)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bsz, n // rows, lane_rows // block),
        in_specs=[
            pl.BlockSpec((1, rows, d_pad // LANES, LANES),
                         lambda b, g, c, slots: (b, g, 0, 0)),
            # the task's rows, read in place at its arena slot
            pl.BlockSpec((1, rows, block, LANES),
                         lambda b, g, c, slots: (slots[b], g, c, 0)),
            pl.BlockSpec((1, rows, block, LANES),
                         lambda b, g, c, slots: (slots[b], g, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, 2, bins, LANES),
                               lambda b, g, c, slots: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((RATING_TERMS + month_terms(grid), block, LANES),
                       jnp.bfloat16),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, 2, bins, LANES), jnp.float32),
        interpret=interpret,
        name="grid_stat_wave",
    )(slots, indices, data, months)
    return out[:, :, :grid, 0]
