"""Public flash-attention op over [B, H, S, D] with GQA head expansion."""

from __future__ import annotations

import jax.numpy as jnp

from .kernel import flash_attention_bhsd
from .ref import attention_ref


def flash_attention(
    q: jnp.ndarray,   # [B, Hq, S, D]
    k: jnp.ndarray,   # [B, Hkv, T, D]
    v: jnp.ndarray,   # [B, Hkv, T, D]
    *,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    if hkv != hq:
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    out = flash_attention_bhsd(
        q.reshape(b * hq, s, d), k.reshape(b * hq, -1, d), v.reshape(b * hq, -1, d),
        causal=causal, block_q=min(block_q, s), block_k=min(block_k, k.shape[-2]),
        interpret=interpret,
    )
    return out.reshape(b, hq, s, d)


def attention_reference(q, k, v, *, causal: bool = True) -> jnp.ndarray:
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    if hkv != hq:
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    out = attention_ref(q.reshape(b * hq, s, d), k.reshape(b * hq, -1, d),
                        v.reshape(b * hq, -1, d), causal=causal)
    return out.reshape(b, hq, s, d)
