"""Public SSD op + the XLA-level chunked fallback used inside jit graphs.

The Pallas kernel (`kernel.ssd_scan`) is the TPU deployment path, validated
in interpret mode.  ``ssd_chunked`` is mathematically the same chunked
algorithm expressed with `lax.scan` over chunks — used by the model code so
the multi-pod dry-run lowers on any backend with the same FLOP/byte shape.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import ssd_scan
from .ref import ssd_ref


@functools.partial(jax.jit, static_argnames=("chunk", "return_state"))
def ssd_chunked(x, dt, a, b, c, *, chunk: int = 128, return_state: bool = False):
    """Chunk-parallel SSD in pure jnp (same math as the Pallas kernel)."""
    bh, s, p = x.shape
    n = b.shape[-1]
    assert s % chunk == 0
    nc = s // chunk
    xc = x.reshape(bh, nc, chunk, p).astype(jnp.float32)
    dtc = dt.reshape(bh, nc, chunk).astype(jnp.float32)
    bc = b.reshape(bh, nc, chunk, n).astype(jnp.float32)
    cc = c.reshape(bh, nc, chunk, n).astype(jnp.float32)
    da = dtc * a[:, None, None]                       # [BH, NC, L]
    cum = jnp.cumsum(da, axis=-1)
    # intra-chunk causal term
    l_mat = jnp.exp(cum[..., :, None] - cum[..., None, :])
    mask = jnp.tril(jnp.ones((chunk, chunk), bool))
    l_mat = jnp.where(mask, l_mat, 0.0)
    scores = jnp.einsum("hcin,hcjn->hcij", cc, bc) * l_mat * dtc[..., None, :]
    y_intra = jnp.einsum("hcij,hcjp->hcip", scores, xc)
    # inter-chunk recurrence over [P, N] states
    total = cum[..., -1]                              # [BH, NC]
    w = jnp.exp(total[..., None] - cum) * dtc         # [BH, NC, L]
    chunk_state = jnp.einsum("hcjp,hcjn->hcpn", xc * w[..., None], bc)

    def carry_fn(state, inp):
        tot, cst = inp
        new = state * jnp.exp(tot)[:, None, None] + cst
        return new, state                              # emit state *before* chunk

    init = jnp.zeros((bh, p, n), jnp.float32)
    final_state, states_in = jax.lax.scan(
        carry_fn, init, (jnp.moveaxis(total, 1, 0), jnp.moveaxis(chunk_state, 1, 0)))
    states_in = jnp.moveaxis(states_in, 0, 1)          # [BH, NC, P, N]
    y_inter = jnp.einsum("hcin,hcpn->hcip", cc * jnp.exp(cum)[..., None], states_in)
    y = (y_intra + y_inter).reshape(bh, s, p).astype(x.dtype)
    if return_state:
        return y, final_state
    return y


def ssd(x, dt, a, b, c, *, chunk: int = 128, use_pallas: bool = False,
        interpret: bool = False) -> jnp.ndarray:
    if use_pallas:
        return ssd_scan(x, dt, a, b, c, chunk=chunk, interpret=interpret)
    return ssd_chunked(x, dt, a, b, c, chunk=chunk)


def ssd_reference(x, dt, a, b, c) -> jnp.ndarray:
    return ssd_ref(x, dt, a, b, c)


def ssd_decode_step(state, x_t, dt_t, a, b_t, c_t):
    """Single-token recurrence for serving.  state [BH,P,N] -> (state, y [BH,P])."""
    decay = jnp.exp(dt_t * a)[:, None, None]
    state = state * decay + dt_t[:, None, None] * jnp.einsum("hp,hn->hpn", x_t, b_t)
    y = jnp.einsum("hpn,hn->hp", state, c_t)
    return state, y
