"""Blockwise (flash) attention Pallas kernel with online softmax.

TPU-native tiling: the KV sequence streams through VMEM in ``block_k`` tiles
while running max/denominator/accumulator live in VMEM scratch across the
innermost (sequential) grid dimension.  MXU-aligned blocks (multiples of 128)
keep the two matmuls on the systolic array.  Causal masking is applied
in-block; fully-masked blocks still flow through the grid (masked to -inf),
which keeps the index maps trivial — the XLA-level fallback used for the
dry-run (`repro.models.attention.blockwise_attention`) has the same FLOP
shape, so roofline numbers transfer.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
MIN_LANE = 128


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, acc, m_scr, l_scr, *, scale, causal, block_q, block_k):
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    q = q_ref[0].astype(jnp.float32)                    # [bq, D]
    k = k_ref[0].astype(jnp.float32)                    # [bk, D]
    v = v_ref[0].astype(jnp.float32)                    # [bk, D]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale  # [bq, bk]
    if causal:
        rows = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(rows >= cols, s, NEG_INF)

    m_prev = m_scr[...][:, :1]                          # [bq, 1]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)                              # [bq, bk]
    alpha = jnp.exp(m_prev - m_new)                     # [bq, 1]
    l_prev = l_scr[...][:, :1]
    l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
    acc[...] = acc[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ik == pl.num_programs(2) - 1)
    def _flush():
        o_ref[0] = (acc[...] / jnp.maximum(l_scr[...][:, :1], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret"))
def flash_attention_bhsd(
    q: jnp.ndarray,   # [BH, S, D]
    k: jnp.ndarray,   # [BH, T, D]
    v: jnp.ndarray,   # [BH, T, D]
    *,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    bh, s, d = q.shape
    t = k.shape[1]
    assert s % block_q == 0 and t % block_k == 0, (s, t, block_q, block_k)
    scale = 1.0 / (d ** 0.5)
    kern = functools.partial(_attn_kernel, scale=scale, causal=causal,
                             block_q=block_q, block_k=block_k)
    return pl.pallas_call(
        kern,
        grid=(bh, s // block_q, t // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, MIN_LANE), jnp.float32),
            pltpu.VMEM((block_q, MIN_LANE), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
