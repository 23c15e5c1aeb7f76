"""Payload quantise/pack Pallas kernel — SPAC's protocol compression on TPU.

The paper shrinks protocol headers (42 B → 2 B) at compile time; the TPU
analogue compresses the *payload* of comm-layer messages (gradient buckets,
MoE dispatch tokens): bf16/f32 tensors are quantised to int8 with one f32
scale per 128-element group, cutting collective bytes ~2×(bf16) / ~3.6×(f32
with scales).  ``dequantize`` is the receive-side parser.

Tiling: rows are processed in blocks of ``block_rows``; the last dim must be
a multiple of the 128-lane group so the absmax reduction stays within a
vector register tile (MXU-free, pure VPU kernel).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

GROUP = 128  # quantisation group = one VREG lane row


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)                   # [bр, G*k]
    r, c = x.shape
    g = x.reshape(r, c // GROUP, GROUP)
    absmax = jnp.max(jnp.abs(g), axis=-1, keepdims=True) # [r, c/G, 1]
    scale = jnp.maximum(absmax, 1e-30) / 127.0
    q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int8)
    q_ref[...] = q.reshape(r, c)
    s_ref[...] = scale[..., 0]


def _dequant_kernel(q_ref, s_ref, x_ref):
    q = q_ref[...].astype(jnp.float32)
    r, c = q.shape
    g = q.reshape(r, c // GROUP, GROUP)
    x = g * s_ref[...][..., None]
    x_ref[...] = x.reshape(r, c).astype(x_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def quantize(x: jnp.ndarray, *, block_rows: int = 256, interpret: bool = False):
    """x [R, C] (C % 128 == 0) -> (q int8 [R, C], scales f32 [R, C/128])."""
    r, c = x.shape
    assert c % GROUP == 0, f"last dim {c} must be a multiple of {GROUP}"
    br = min(block_rows, r)
    assert r % br == 0, f"rows {r} not divisible by block {br}"
    q, s = pl.pallas_call(
        _quant_kernel,
        grid=(r // br,),
        in_specs=[pl.BlockSpec((br, c), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((br, c), lambda i: (i, 0)),
            pl.BlockSpec((br, c // GROUP), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r, c), jnp.int8),
            jax.ShapeDtypeStruct((r, c // GROUP), jnp.float32),
        ],
        interpret=interpret,
    )(x)
    return q, s


@functools.partial(jax.jit, static_argnames=("block_rows", "out_dtype", "interpret"))
def dequantize(q: jnp.ndarray, s: jnp.ndarray, *, block_rows: int = 256,
               out_dtype=jnp.float32, interpret: bool = False):
    r, c = q.shape
    br = min(block_rows, r)
    assert r % br == 0
    return pl.pallas_call(
        _dequant_kernel,
        grid=(r // br,),
        in_specs=[
            pl.BlockSpec((br, c), lambda i: (i, 0)),
            pl.BlockSpec((br, c // GROUP), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((br, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, c), out_dtype),
        interpret=interpret,
    )(q, s)
