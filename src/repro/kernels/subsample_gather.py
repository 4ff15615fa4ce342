"""Subsample-gather kernels (Pallas, TPU target) — the paper's map task.

Random-subsample statistics need ``rows = data[indices]; stats(rows)`` where
``indices`` are random (the cache-hostile pattern of thesis Fig 2).

``subsample_stats_wave`` — the platform's kernel, serving the ``pallas``
engine of the ``moments`` statistic.  It batches a whole *wave* of tasks
behind one leading grid dimension and returns only the per-task
statistics (Σrow, Σrow²): the gathered rows are never written back.
Its data live in the **lane layout** ``[S, N, L, 128]``: each padded row
of ``W = 128·L`` values is stored as ``L`` lane rows of 128, so a drawn
row is a slice of the leading, untiled ``N`` dimension and its DMA is
tile-aligned (a ``[1, W]`` slice of a ``[N, W]`` array is not: Mosaic
tiles the last two dimensions by (8, 128)).  The platform's block arena
is packed in this layout (:func:`to_lane_layout`) and the wave's slot
vector is a prefetched scalar, so a wave reads the drawn rows straight
out of the arena, with no relayout and no gathered copy of its blocks.
Each grid step DMAs ``ROWS_PER_STEP`` drawn
rows HBM→VMEM back-to-back and folds them into the output block, which
stays resident across the step axis.  A row wider than the VMEM budget
is split over a column-block grid axis (:func:`column_block`); every
column still sees its rows in index order, so a task's result does not
depend on the wave it rides in or on the column split — a wave is
bit-identical to the same tasks run as waves of one.

``subsample_gather`` — the gathered-output variant (scalar-prefetch
``PrefetchScalarGridSpec`` row pipeline writing ``[T, D]`` plus the
statistics).  Its ``(1, D)`` blocks break Mosaic's (8, 128) block rule,
so it runs in interpret mode only; the platform never calls it.

Both are validated in interpret mode against ``ref.subsample_stats_ref``
and :func:`subsample_stats_wave` compiles for TPU v5e at the platform's
widths (``tests/test_tpu_compile.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gather_kernel(idx_ref, nvalid_ref, row_ref, gathered_ref, stats_ref,
                   acc_ref, *, n_idx: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    row = row_ref[0].astype(jnp.float32)            # [D]
    gathered_ref[0] = row.astype(gathered_ref.dtype)

    @pl.when(i < nvalid_ref[0])                     # padded tail: no stats
    def _accumulate():
        acc_ref[0, :] += row
        acc_ref[1, :] += row * row

    @pl.when(i == n_idx - 1)
    def _finalize():
        stats_ref[...] = acc_ref[...].astype(stats_ref.dtype)


def subsample_gather(
    data: jax.Array,          # [N, D] the task's working set
    indices: jax.Array,       # [T] int32 random row ids (may be padded)
    n_valid: jax.Array,       # [1] int32: only indices[:n_valid] accumulate
    *,
    interpret: bool,
):
    """Returns (gathered [T, D], stats [2, D]) with stats = (Σrow, Σrow²)
    over the first ``n_valid`` rows.  Rows past ``n_valid`` are still
    gathered (callers slice them off) but masked out of the statistics, so
    ``indices`` can be padded to a canonical length without retracing."""
    n, d = data.shape
    t = indices.shape[0]
    kernel = functools.partial(_gather_kernel, n_idx=t)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(t,),
        in_specs=[
            # one data row per grid step, chosen by the prefetched index —
            # the DMA for step i+1 overlaps step i's reduction
            pl.BlockSpec((1, d), lambda i, idx_ref, nv_ref: (idx_ref[i], 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, d), lambda i, idx_ref, nv_ref: (i, 0)),
            pl.BlockSpec((2, d), lambda i, idx_ref, nv_ref: (0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((2, d), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((t, d), data.dtype),
            jax.ShapeDtypeStruct((2, d), jnp.float32),
        ],
        interpret=interpret,
    )(indices, n_valid, data)


LANES = 128                  # last-dimension tile of a TPU vreg
SUBLANES = 8                 # second-to-last tile of a float32 vreg
ROWS_PER_STEP = 8            # drawn rows DMA'd per grid step
# VMEM for one grid step's drawn-row buffer; the output block (double
# buffered, 2 × 2 × block) adds half as much again, which keeps a step
# well inside v5e's 16 MiB default scoped VMEM
ROW_BUFFER_BYTES = 4 << 20


def column_block(lane_rows: int) -> int:
    """Lane rows per column block: the whole row when ``ROWS_PER_STEP``
    of them fit :data:`ROW_BUFFER_BYTES`, else the largest multiple of
    the sublane tile that does."""
    fit = ROW_BUFFER_BYTES // (ROWS_PER_STEP * LANES * 4)
    if lane_rows <= fit:
        return lane_rows
    return fit // SUBLANES * SUBLANES


def lane_width(d: int) -> int:
    """Columns a row of ``d`` values occupies in the lane layout: ``d``
    rounded up to whole lane rows, and to whole column blocks when the
    row is split (a no-op for the power-of-two widths the platform
    pads to)."""
    rows = -(-d // LANES)
    block = column_block(rows)
    return -(-rows // block) * block * LANES


def to_lane_layout(x):
    """``[..., D]`` → ``[..., L, 128]``, zero-padding columns up to
    :func:`lane_width`.  Works on NumPy and JAX arrays alike; the padded
    columns' statistics are sliced off by the caller."""
    d = x.shape[-1]
    w = lane_width(d)
    if w != d:
        pad = [(0, 0)] * (x.ndim - 1) + [(0, w - d)]
        x = jnp.pad(x, pad) if isinstance(x, jax.Array) else np.pad(x, pad)
    return x.reshape(*x.shape[:-1], w // LANES, LANES)


def _stats_wave_kernel(slots_ref, idx_ref, data_ref, stats_ref, rows_ref,
                       sems, *, n_idx: int, block: int, split: bool,
                       chunk: int):
    b = pl.program_id(0)                            # task within the wave
    c = pl.program_id(1)                            # column block
    s = pl.program_id(2)                            # row group within task

    @pl.when(s == 0)
    def _init():
        stats_ref[...] = jnp.zeros_like(stats_ref)

    # the drawn row of the task's arena slot is a leading-dimension slice
    # ([L, 128], tile-aligned); a split row takes column block c of it
    def row_dma(j: int):
        src = data_ref.at[slots_ref[b], idx_ref[b, s * ROWS_PER_STEP + j]]
        if split:
            src = src.at[pl.ds(pl.multiple_of(c * block, SUBLANES), block)]
        return pltpu.make_async_copy(src, rows_ref.at[j], sems.at[j])

    for j in range(ROWS_PER_STEP):
        row_dma(j).start()
    for j in range(ROWS_PER_STEP):
        row_dma(j).wait()

    n_valid = n_idx - s * ROWS_PER_STEP             # < R only on the tail

    def fold(k, carry):
        cols = pl.ds(pl.multiple_of(k * chunk, chunk), chunk)
        rows = rows_ref[:, cols, :]                 # [R, chunk, 128]
        valid = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 0) < n_valid
        rows = jnp.where(valid, rows, 0.0)
        stats_ref[0, 0, cols, :] += jnp.sum(rows, axis=0)
        stats_ref[0, 1, cols, :] += jnp.sum(rows * rows, axis=0)
        return carry

    jax.lax.fori_loop(0, block // chunk, fold, 0)


def subsample_stats_wave(
    data: jax.Array,          # [S, N, L, 128] float32 task blocks, lane layout
    slots: jax.Array,         # [B] int32: the block of each task in the wave
    indices: jax.Array,       # [B, T] int32 random row ids per task
    *,
    interpret: bool,
) -> jax.Array:
    """Stats-only wave gather: returns stats ``[B, 2, L, 128]`` =
    per-task (Σrow, Σrow²) over rows ``indices[b]`` of block
    ``data[slots[b]]``, in the lane layout.  The slots are prefetched
    scalars, so the kernel reads the drawn rows straight out of the
    whole ``data`` (the platform's resident arena) and no ``[B, ...]``
    copy of the wave's blocks is ever made.  ``T`` is rounded up to a
    multiple of :data:`ROWS_PER_STEP` internally (tail masked)."""
    _s, _n, lane_rows, lanes = data.shape
    assert lanes == LANES, data.shape
    bsz, t = indices.shape
    assert slots.shape == (bsz,), (slots.shape, bsz)
    block = column_block(lane_rows)
    assert lane_rows % block == 0, (lane_rows, block)
    t_pad = -(-t // ROWS_PER_STEP) * ROWS_PER_STEP
    if t_pad != t:
        indices = jnp.pad(indices, ((0, 0), (0, t_pad - t)))
    kernel = functools.partial(
        _stats_wave_kernel, n_idx=t, block=block,
        split=block != lane_rows,
        chunk=SUBLANES if block % SUBLANES == 0 else block)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bsz, lane_rows // block, t_pad // ROWS_PER_STEP),
        in_specs=[
            # the arena stays in HBM; the kernel DMAs the drawn rows itself
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, 2, block, LANES),
                               lambda b, c, s, *_: (b, 0, c, 0)),
        scratch_shapes=[
            pltpu.VMEM((ROWS_PER_STEP, block, LANES), jnp.float32),
            pltpu.SemaphoreType.DMA((ROWS_PER_STEP,)),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, 2, lane_rows, LANES),
                                       jnp.float32),
        interpret=interpret,
        name="subsample_stats_wave",
    )(slots, indices, data)
