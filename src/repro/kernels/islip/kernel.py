"""Batched iSLIP matching as a Pallas kernel — the paper's scheduler on the VPU.

The DSE's brute-force stage and the surrogate calibration both want to
arbitrate *many* switch instances per step (one per candidate × traffic
window).  A single matching is tiny (an [N, N] bit matrix), so the kernel
batches: each grid step arbitrates ``block_b`` independent switches held in
one VMEM tile, with the request/grant/accept iterations fully unrolled
(iters is compile-time, like the paper's HLS template parameter).

Contract (per batch row): requests [N, N] int32 (0/1), grant/accept pointers
[N] int32 → match [N, N] int32 one-hot matching + updated pointers
(McKeown's rule: pointers advance only on a first-iteration accepted grant).
N is padded to the 128-lane boundary by ``ops.islip_schedule``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BIG = 1 << 20  # plain int: jnp constants would be captured consts in the kernel


def _rot_pick_rows(v, p, n_valid):
    """One-hot first set bit at/after rotating pointer, per row.

    v [B, R, C] int32 0/1; p [B, R] int32 -> one-hot [B, R, C]."""
    c = v.shape[-1]
    idx = jax.lax.broadcasted_iota(jnp.int32, v.shape, 2)
    in_range = idx < n_valid
    score = jnp.where((v > 0) & in_range, (idx - p[..., None]) % n_valid, BIG)
    best = jnp.min(score, axis=-1, keepdims=True)
    pick = (score == best) & (best < BIG)
    # break ties (can't happen for distinct mod values, but keep it safe)
    first = jnp.cumsum(pick.astype(jnp.int32), axis=-1) == 1
    return (pick & first).astype(jnp.int32)


def _islip_kernel(req_ref, gptr_ref, aptr_ref, match_ref, gout_ref, aout_ref,
                  *, iters: int, n_valid: int):
    req = req_ref[...].astype(jnp.int32)        # [B, N, N]
    gptr = gptr_ref[...].astype(jnp.int32)      # [B, N]
    aptr = aptr_ref[...].astype(jnp.int32)
    b, n, _ = req.shape
    match = jnp.zeros_like(req)
    new_g, new_a = gptr, aptr
    for it in range(iters):                     # unrolled (template parameter)
        row_busy = (match.sum(2) > 0)[:, :, None]
        col_busy = (match.sum(1) > 0)[:, None, :]
        free = req * (1 - row_busy.astype(jnp.int32)) * (1 - col_busy.astype(jnp.int32))
        # grant: each output (column) picks a requesting input
        grants_t = _rot_pick_rows(free.transpose(0, 2, 1), gptr, n_valid)
        grants = grants_t.transpose(0, 2, 1)    # [B, N_in, N_out]
        # accept: each input (row) picks among its grants
        accepts = _rot_pick_rows(grants, aptr, n_valid)
        match = match + accepts
        if it == 0:                             # McKeown's pointer rule
            out_accepted = accepts.sum(1)       # [B, N_out] 0/1
            in_accepted = accepts.sum(2)        # [B, N_in]
            g_in = jnp.argmax(accepts, axis=1).astype(jnp.int32)   # per output
            a_out = jnp.argmax(accepts, axis=2).astype(jnp.int32)  # per input
            new_g = jnp.where(out_accepted > 0, (g_in + 1) % n_valid, gptr)
            new_a = jnp.where(in_accepted > 0, (a_out + 1) % n_valid, aptr)
    match_ref[...] = match
    gout_ref[...] = new_g
    aout_ref[...] = new_a


@functools.partial(jax.jit, static_argnames=("iters", "n_valid", "block_b", "interpret"))
def islip_schedule_padded(
    req: jnp.ndarray,    # [B, Np, Np] int32 (padded to 128 lanes)
    gptr: jnp.ndarray,   # [B, Np] int32
    aptr: jnp.ndarray,   # [B, Np] int32
    *,
    iters: int = 2,
    n_valid: int = 16,
    block_b: int = 8,
    interpret: bool = False,
):
    b, np_, _ = req.shape
    assert b % block_b == 0, (b, block_b)
    kern = functools.partial(_islip_kernel, iters=iters, n_valid=n_valid)
    return pl.pallas_call(
        kern,
        grid=(b // block_b,),
        in_specs=[
            pl.BlockSpec((block_b, np_, np_), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_b, np_), lambda i: (i, 0)),
            pl.BlockSpec((block_b, np_), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_b, np_, np_), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_b, np_), lambda i: (i, 0)),
            pl.BlockSpec((block_b, np_), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, np_, np_), jnp.int32),
            jax.ShapeDtypeStruct((b, np_), jnp.int32),
            jax.ShapeDtypeStruct((b, np_), jnp.int32),
        ],
        interpret=interpret,
    )(req, gptr, aptr)
