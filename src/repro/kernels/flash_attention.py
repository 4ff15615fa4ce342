"""Flash-attention forward kernel (Pallas, TPU target).

Blocked causal attention with online softmax.  VMEM working set per grid
step is ``bq·hd + bk·hd·2 + bq·bk`` floats — block sizes are chosen by the
kneepoint tuner so this sits under the VMEM knee (the paper's task-sizing
rule applied to attention tiles; DESIGN.md §3).

Grid: ``(batch·kv_heads·q_per_kv, n_q_blocks, n_kv_blocks)`` with the KV
axis innermost and *sequential*, carrying the online-softmax state
``(m, l, acc)`` in VMEM scratch across KV steps.  Causal masking skips
fully-masked KV blocks via ``pl.when`` (no FLOPs wasted beyond the
diagonal).  MXU contractions are ``[bq,hd]@[hd,bk]`` and ``[bq,bk]@[bk,hd]``
— hardware-aligned when bq,bk,hd are multiples of 128 (the defaults).

Validated in interpret mode against ``ref.flash_attention_ref``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  bq: int, bk: int, scale: float, causal: bool,
                  n_kv_blocks: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # block [bq] query rows start at qi*bq; kv cols start at ki*bk
    run = (not causal) or (ki * bk <= qi * bq + bq - 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                 # [bq, hd]
        k = k_ref[0].astype(jnp.float32)                 # [bk, hd]
        v = v_ref[0].astype(jnp.float32)                 # [bk, hd]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]
        if causal:
            rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(cols <= rows, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        correction = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * correction + jnp.sum(p, axis=1)
        acc_ref[...] = (acc_ref[...] * correction[:, None]
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(ki == n_kv_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,            # [BH, Sq, HD]
    k: jax.Array,            # [BH, Skv, HD]
    v: jax.Array,            # [BH, Skv, HD]
    *,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool,
) -> jax.Array:
    bh, sq, hd = q.shape
    _, skv, _ = k.shape
    bq = min(block_q, sq)
    bk = min(block_k, skv)
    assert sq % bq == 0 and skv % bk == 0, (sq, bq, skv, bk)
    n_q, n_kv = sq // bq, skv // bk
    scale = 1.0 / math.sqrt(hd)
    kernel = functools.partial(
        _flash_kernel, bq=bq, bk=bk, scale=scale, causal=causal,
        n_kv_blocks=n_kv)
    return pl.pallas_call(
        kernel,
        grid=(bh, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),       # m (running max)
            pltpu.VMEM((bq,), jnp.float32),       # l (running denom)
            pltpu.VMEM((bq, hd), jnp.float32),    # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
