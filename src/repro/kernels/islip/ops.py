"""Public batched-iSLIP op: pad ports to the lane boundary, run the kernel."""

from __future__ import annotations

import jax.numpy as jnp

from .kernel import islip_schedule_padded
from .ref import islip_ref

LANES = 128


def islip_schedule(req, gptr, aptr, *, iters: int = 2, use_pallas: bool = True,
                   interpret: bool = False):
    """req [B, N, N] -> (match, gptr', aptr').  N padded to 128 internally."""
    b, n, _ = req.shape
    if not use_pallas:
        return islip_ref(req, gptr, aptr, iters=iters)
    np_ = -(-n // LANES) * LANES
    rq = jnp.zeros((b, np_, np_), jnp.int32).at[:, :n, :n].set(req.astype(jnp.int32))
    g = jnp.zeros((b, np_), jnp.int32).at[:, :n].set(gptr.astype(jnp.int32))
    a = jnp.zeros((b, np_), jnp.int32).at[:, :n].set(aptr.astype(jnp.int32))
    bb = 8
    pad_b = (-b) % bb
    if pad_b:
        rq = jnp.pad(rq, ((0, pad_b), (0, 0), (0, 0)))
        g = jnp.pad(g, ((0, pad_b), (0, 0)))
        a = jnp.pad(a, ((0, pad_b), (0, 0)))
    m, g2, a2 = islip_schedule_padded(rq, g, a, iters=iters, n_valid=n,
                                      block_b=bb, interpret=interpret)
    return m[:b, :n, :n], g2[:b, :n], a2[:b, :n]
