"""Batched greedy-crossbar contention as a Pallas kernel.

One matching step is tiny (two [N, B] slack arrays), so — like the iSLIP
kernel — the grid runs over *candidate blocks* × *event blocks*: each
program keeps the input/output port-slack state for ``block_b`` candidates
resident in VMEM scratch and walks its ``block_m`` events of the shared
trace with a ``fori_loop``, admitting one packet per iteration lane-parallel
across the candidates.  The event axis is the grid's sequential
("arbitrary") axis, so the scratch carries port state across event blocks.

Layout: ``dt``/``src``/``dst`` are 1-D SMEM blocks read as scalars;
``svc``/``dep`` are event-major ``[m, B]`` (candidates on the lane axis), so
each event reads and writes one dynamic sublane row; ports sit on the
sublane axis of the ``[n_pad, block_b]`` state (padded to a multiple of 8 by
``ops.xbar_contend``) and are gathered/scattered with a sublane-iota mask.

Contract: dt [m] float32, src/dst [m] int32 shared; svc [m, B] float32
per-candidate → dep [m, B] float32 departure offsets; ``m`` a multiple of
``block_m`` and ``B`` of ``block_b``.  Implements the slack formulation
(``ref.xbar_contend_slack_ref``) — the carries never hold absolute
timestamps, so float32 survives long traces — and matches that oracle
bit-for-bit in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _xbar_kernel(dt_ref, src_ref, dst_ref, svc_ref, dep_ref, in_s, out_s, *,
                 block_m: int):
    @pl.when(pl.program_id(1) == 0)
    def _():
        in_s[...] = jnp.zeros_like(in_s)
        out_s[...] = jnp.zeros_like(out_s)

    port = jax.lax.broadcasted_iota(jnp.int32, in_s.shape, 0)   # [Np, B]

    def body(k, _):
        dtk = dt_ref[k]
        i = src_ref[k]
        j = dst_ref[k]
        s = svc_ref[pl.ds(k, 1), :]                             # [1, B]
        ins = jnp.maximum(in_s[...] - dtk, 0.0)
        outs = jnp.maximum(out_s[...] - dtk, 0.0)
        wait = jnp.maximum(
            jnp.max(jnp.where(port == i, ins, 0.0), axis=0, keepdims=True),
            jnp.max(jnp.where(port == j, outs, 0.0), axis=0, keepdims=True),
        )
        dep = wait + s                                          # [1, B]
        in_s[...] = jnp.where(port == i, dep, ins)
        out_s[...] = jnp.where(port == j, dep, outs)
        dep_ref[pl.ds(k, 1), :] = dep
        return 0

    jax.lax.fori_loop(0, block_m, body, 0)


@functools.partial(jax.jit, static_argnames=("n_pad", "block_b", "block_m",
                                             "interpret"))
def xbar_contend_padded(
    dt: jnp.ndarray,     # [m] float32
    src: jnp.ndarray,    # [m] int32
    dst: jnp.ndarray,    # [m] int32
    svc: jnp.ndarray,    # [m, B] float32 (event-major)
    *,
    n_pad: int,          # ports padded to the sublane boundary
    block_b: int = 128,
    block_m: int = 1024,
    interpret: bool = False,
):
    m, b = svc.shape
    assert b % block_b == 0 and m % block_m == 0, (m, b, block_m, block_b)
    scalars = pl.BlockSpec((block_m,), lambda c, e: (e,),
                           memory_space=pltpu.SMEM)
    events = pl.BlockSpec((block_m, block_b), lambda c, e: (e, c))
    return pl.pallas_call(
        functools.partial(_xbar_kernel, block_m=block_m),
        grid=(b // block_b, m // block_m),
        in_specs=[scalars, scalars, scalars, events],
        out_specs=events,
        out_shape=jax.ShapeDtypeStruct((m, b), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((n_pad, block_b), jnp.float32),
            pltpu.VMEM((n_pad, block_b), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(dt, src, dst, svc)
