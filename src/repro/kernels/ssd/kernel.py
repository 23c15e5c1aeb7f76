"""Mamba-2 SSD (state-space duality) chunked-scan Pallas kernel.

The SSD recurrence  h_t = exp(dt_t·A)·h_{t-1} + dt_t·(x_t ⊗ B_t),
y_t = h_t·C_t  is computed chunk-parallel: within a length-``L`` chunk the
output is an attention-like causal matmul (MXU-friendly), and only one
[P, N] state matrix crosses chunk boundaries — carried in VMEM scratch across
the sequential chunk grid dimension.  This is the TPU-native re-blocking of
the CUDA chunked scan: chunk length is chosen so (L×P + L×N + P×N) tiles fit
VMEM with MXU-aligned L, P, N (multiples of 128 where possible).

Shapes: x [BH, S, P] (P = head dim), dt [BH, S], a [BH] (per-head decay,
A = -exp(A_log)), B/C [BH, S, N] (state dim N).  Output y [BH, S, P].
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, o_ref, state, *, chunk):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        state[...] = jnp.zeros_like(state)

    x = x_ref[0].astype(jnp.float32)          # [L, P]
    dt = dt_ref[0].astype(jnp.float32)        # [L, lanes] (value replicated)
    dt = dt[:, :1]                            # [L, 1]
    a = a_ref[0, 0]                           # scalar
    b = b_ref[0].astype(jnp.float32)          # [L, N]
    c = c_ref[0].astype(jnp.float32)          # [L, N]

    da = dt[:, 0] * a                         # [L] (a < 0 ⇒ decays)
    cum = jnp.cumsum(da)                      # inclusive cumulative log-decay
    # ---- intra-chunk (attention-like) term: i attends to j <= i
    l_mat = jnp.exp(cum[:, None] - cum[None, :])
    rows = jax.lax.broadcasted_iota(jnp.int32, l_mat.shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, l_mat.shape, 1)
    l_mat = jnp.where(rows >= cols, l_mat, 0.0)
    scores = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # [L, L]
    scores = scores * l_mat * dt[None, :, 0]
    y = jax.lax.dot_general(scores, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)       # [L, P]
    # ---- inter-chunk: contribution of the carried state
    c_scaled = c * jnp.exp(cum)[:, None]                               # [L, N]
    y = y + jax.lax.dot_general(c_scaled, state[...], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)    # [L, P]
    # ---- state update: decay over the whole chunk + inject chunk inputs
    total = cum[-1]
    w = jnp.exp(total - cum) * dt[:, 0]                                # [L]
    state[...] = state[...] * jnp.exp(total) + jax.lax.dot_general(
        x * w[:, None], b, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                            # [P, N]
    o_ref[0] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(
    x: jnp.ndarray,    # [BH, S, P]
    dt: jnp.ndarray,   # [BH, S]
    a: jnp.ndarray,    # [BH]
    b: jnp.ndarray,    # [BH, S, N]
    c: jnp.ndarray,    # [BH, S, N]
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    bh, s, p = x.shape
    n = b.shape[-1]
    assert s % chunk == 0, (s, chunk)
    lanes = 128
    dt_pad = jnp.broadcast_to(dt[..., None], (bh, s, lanes))
    a_pad = jnp.broadcast_to(a[:, None], (bh, lanes))
    kern = functools.partial(_ssd_kernel, chunk=chunk)
    return pl.pallas_call(
        kern,
        grid=(bh, s // chunk),
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, chunk, lanes), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, lanes), lambda h, i: (h, 0)),
            pl.BlockSpec((1, chunk, n), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, chunk, n), lambda h, i: (h, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, p), lambda h, i: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(x, dt_pad, a_pad, b, c)
