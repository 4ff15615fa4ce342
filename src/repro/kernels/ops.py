"""Jitted public wrappers around the Pallas kernels.

Each wrapper lowers its kernel for the platform the program is compiled
for, through ``jax.lax.platform_dependent``: compiled by Mosaic on TPU,
run by the Pallas interpreter on CPU, and refused at lowering time on
any other backend.  The choice is made per lowering, not at import, so a
TPU that failed to initialise can never leave a kernel silently
interpreted on the CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import grid_stat as _gs
from repro.kernels import rglru_scan as _rg
from repro.kernels import rwkv6_scan as _rw
from repro.kernels import subsample_gather as _sg


def _lowered(kernel, *args, **static):
    """Call ``kernel(*args, **static, interpret=...)`` with ``interpret``
    fixed by the platform being lowered for (TPU: compiled, CPU:
    interpreted)."""
    return jax.lax.platform_dependent(
        *args,
        tpu=functools.partial(kernel, **static, interpret=False),
        cpu=functools.partial(kernel, **static, interpret=True))


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k"))
def flash_attention(q, k, v, *, causal=True, block_q=128, block_k=128):
    return _lowered(_fa.flash_attention, q, k, v, causal=causal,
                    block_q=block_q, block_k=block_k)


@functools.partial(jax.jit, static_argnames=("chunk",))
def rwkv6_chunked(r, k, v, logw, u, *, chunk=64):
    return _lowered(_rw.rwkv6_chunked, r, k, v, logw, u, chunk=chunk)


@functools.partial(jax.jit, static_argnames=("chunk", "width_block"))
def rglru_scan(a, b, h0, *, chunk=128, width_block=256):
    return _lowered(_rg.rglru_scan, a, b, h0, chunk=chunk,
                    width_block=width_block)


@jax.jit
def _subsample_gather_padded(data, indices, n_valid):
    return _lowered(_sg.subsample_gather, data, indices, n_valid)


def _pow2(n: int) -> int:
    # keep in sync with repro.platform.compute.pow2_ceil — the kernels
    # layer must stay importable without the platform package
    return 1 << (max(n, 1) - 1).bit_length()


def subsample_gather(data, indices):
    """(gathered [T, D], stats [2, D]) for random row ids ``indices``.

    The index count is rounded up to a power of two *outside* the jit
    boundary (tail masked out of the accumulator by the kernel, padded
    gathered rows sliced off here), so one compiled kernel serves every
    draw count of a given padded length instead of retracing per ``T``.
    Mosaic refuses this kernel's ``(1, D)`` blocks, so it runs on CPU
    only; the platform never calls it.
    """
    t = indices.shape[0]
    t_pad = _pow2(t)
    if t_pad != t:
        indices = jnp.pad(indices, (0, t_pad - t))
    n_valid = jnp.full((1,), t, jnp.int32)
    gathered, stats = _subsample_gather_padded(data, indices, n_valid)
    return gathered[:t], stats


def subsample_stats_lanes(data, slots, indices):
    """The platform's wave kernel on blocks already in the lane layout:
    ``data [S, N, L, 128]`` + ``slots [B]`` + ``indices [B, T]`` → stats
    ``[B, 2, L, 128]`` of rows ``indices[b]`` of block ``data[slots[b]]``.
    Not jitted: the platform's wave programs trace it inside their own
    jit (and inside ``shard_map``)."""
    return _lowered(_sg.subsample_stats_wave, data, slots, indices)


@jax.jit
def subsample_stats(data, indices):
    """Stats-only wave gather: data [B, N, D] + indices [B, T] → stats
    [B, 2, D], no gathered output.  Converts to the lane layout and
    back; the platform packs its arena in that layout once and calls
    :func:`subsample_stats_lanes`."""
    d = data.shape[-1]
    slots = jnp.arange(data.shape[0], dtype=jnp.int32)
    stats = subsample_stats_lanes(_sg.to_lane_layout(data), slots, indices)
    return stats.reshape(*stats.shape[:2], -1)[..., :d]


def grid_stat_lanes(data, months, slots, indices, *, grid):
    """The platform's ``monthly_mean`` wave kernel on blocks in the lane
    layout: ratings and months ``[S, N, L, 128]`` + ``slots [B]`` +
    ``indices [B, N, D]`` (drawn columns of each row) → per-task
    ``[B, 2, grid]`` (sum of the drawn ratings, count of draws) per
    month bin.  Not jitted: the platform's wave programs trace it inside
    their own jit (and inside ``shard_map``)."""
    return _lowered(_gs.grid_stat_wave, data, months, slots, indices,
                    grid=grid)
