"""RG-LRU linear-recurrence kernel (Pallas, TPU target).

h_t = a_t ⊙ h_{t−1} + b_t over the sequence, channel-parallel.  Grid:
``(batch, width_blocks, chunks)`` with the chunk axis sequential and the
``[WB]`` hidden state carried in VMEM scratch; within a chunk the
recurrence runs as a ``fori_loop`` over VREG-resident rows.  Chunk length
is the kneepoint-tuned ``cfg.chunk_len`` (tiny tasks over time, working
set = one ``[C, WB]`` tile).

Validated against ``ref.linear_scan_ref`` (associative-scan oracle).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _rglru_kernel(a_ref, b_ref, h0_ref, o_ref, h_ref, *, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = h0_ref[0].astype(jnp.float32)

    a = a_ref[0].astype(jnp.float32)                # [C, WB]
    b = b_ref[0].astype(jnp.float32)

    def step(t, carry):
        h, out = carry
        h = a[t] * h + b[t]
        out = jax.lax.dynamic_update_index_in_dim(out, h, t, 0)
        return h, out

    h, out = jax.lax.fori_loop(
        0, chunk, step, (h_ref[...], jnp.zeros_like(a)))
    h_ref[...] = h
    o_ref[0] = out.astype(o_ref.dtype)


def rglru_scan(
    a: jax.Array,             # [B, S, W] decay in (0,1)
    b: jax.Array,             # [B, S, W] gated input
    h0: jax.Array,            # [B, W] carried state
    *,
    chunk: int = 128,
    width_block: int = 256,
    interpret: bool,
) -> jax.Array:
    bsz, s, w = a.shape
    chunk = min(chunk, s)
    wb = min(width_block, w)
    assert s % chunk == 0 and w % wb == 0, (s, chunk, w, wb)
    kernel = functools.partial(_rglru_kernel, chunk=chunk)
    spec = pl.BlockSpec((1, chunk, wb), lambda bi, wi, ci: (bi, ci, wi))
    return pl.pallas_call(
        kernel,
        grid=(bsz, w // wb, s // chunk),
        in_specs=[spec, spec,
                  pl.BlockSpec((1, wb), lambda bi, wi, ci: (bi, wi))],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((bsz, s, w), jnp.float32),
        scratch_shapes=[pltpu.VMEM((wb,), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b, h0)
