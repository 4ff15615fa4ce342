"""RWKV6 chunked-recurrence kernel (Pallas, TPU target).

One grid step processes one (batch, head, chunk) cell entirely in VMEM:
r/k/v/logw chunk blocks are ``[C, hd]``, the carried state ``[hd, hd]``
lives in VMEM scratch and persists across the *sequential* chunk axis
(innermost grid dimension) — the device-side version of the scheduler's
phase-2 queue: tiny tasks (chunks) run back-to-back against a resident
working set.  Chunk length C is the kneepoint-tuned ``cfg.chunk_len``.

All pairwise decay exponents are ≤ 0 (log-space form, DESIGN.md / rwkv6
module docstring); math mirrors ``repro.models.rwkv6.chunk_body`` and is
validated against ``ref.rwkv6_chunked_ref`` in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _rwkv6_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, s_ref, *,
                  chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    r = r_ref[0, 0].astype(jnp.float32)            # [C, hd]
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    lw = lw_ref[0, 0].astype(jnp.float32)          # log decay ≤ 0
    u = u_ref[0].astype(jnp.float32)               # [1?, hd] bonus
    state = s_ref[...]                             # [hd, hd]

    logp = jnp.cumsum(lw, axis=0) - lw             # exclusive cumsum
    logp_total = logp[-1] + lw[-1]                 # [hd]

    r_dec = r * jnp.exp(logp)
    inter = jax.lax.dot_general(r_dec, state, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)

    logpj1 = logp + lw
    dmat = logp[:, None, :] - logpj1[None, :, :]   # [C, C, hd]
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lower = rows > cols
    dmat = jnp.where(lower[:, :, None], dmat, -jnp.inf)
    amat = jnp.einsum("id,jd,ijd->ij", r, k, jnp.exp(dmat),
                      preferred_element_type=jnp.float32)
    diag = jnp.sum(r * u * k, axis=-1)             # bonus term
    amat = amat + jnp.where(rows == cols, diag[:, None], 0.0)
    intra = jax.lax.dot_general(amat, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)

    k_dec = k * jnp.exp(logp_total[None, :] - logpj1)
    s_ref[...] = (jnp.exp(logp_total)[:, None] * state
                  + jax.lax.dot_general(k_dec, v, (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32))
    o_ref[0, 0] = (inter + intra).astype(o_ref.dtype)


def rwkv6_chunked(
    r: jax.Array,             # [B, H, S, hd]
    k: jax.Array,
    v: jax.Array,
    logw: jax.Array,          # [B, H, S, hd], log decay ≤ 0
    u: jax.Array,             # [H, hd]
    *,
    chunk: int = 64,
    interpret: bool,
) -> jax.Array:
    b, h, s, hd = r.shape
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    n_chunks = s // chunk
    kernel = functools.partial(_rwkv6_kernel, chunk=chunk)
    spec = pl.BlockSpec((1, 1, chunk, hd), lambda bi, hi, ci: (bi, hi, ci, 0))
    return pl.pallas_call(
        kernel,
        grid=(b, h, n_chunks),
        in_specs=[spec, spec, spec, spec,
                  pl.BlockSpec((1, hd), lambda bi, hi, ci: (hi, 0))],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((b, h, s, hd), jnp.float32),
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, logw, u)
