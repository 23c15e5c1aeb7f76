"""Public batched crossbar-contention op.

Dispatch policy: float64 inputs take the absolute-time scan (fastest, and
bit-identical to the serial surrogate's recurrence — it returns *absolute*
departure times so ulp-exact occupancy comparisons hold downstream);
float32 inputs take the slack-form scan whose carries never hold absolute
timestamps, so precision survives long traces — that is also the form the
Pallas kernel implements for TPU deployment (validated in interpret mode on
CPU).  The f32 paths return departure *offsets* (dep - arrival)."""

from __future__ import annotations

import jax.numpy as jnp

from .kernel import xbar_contend_padded
from .ref import xbar_contend_abs_ref, xbar_contend_slack_ref

SUBLANES = 8     # the port axis of the tile's state pads to a multiple
EVENT_BLOCK = 1024   # events per grid step (the SMEM timeline block)


def xbar_contend(t, dt, src, dst, svc, *, n_ports: int, use_pallas: bool = False,
                 block_b: int = 128, interpret: bool = False,
                 absolute: bool = None):
    """t/dt/src/dst [m] shared trace, svc [B, m] -> [B, m] departure times
    (absolute on the float64 path, arrival-relative offsets on float32).

    Pass ``absolute=True`` to *require* absolute-time semantics: if x64 is
    disabled JAX silently downcasts float64 inputs and the dtype dispatch
    would quietly hand back offsets instead — this raises there."""
    is_f64 = jnp.asarray(svc).dtype == jnp.float64
    if absolute is None:
        absolute = is_f64 and not use_pallas
    elif absolute and (use_pallas or not is_f64):
        raise ValueError(
            "absolute departure times need the float64 scan (enable jax x64 "
            f"and use_pallas=False); got dtype {jnp.asarray(svc).dtype}")
    if use_pallas:
        b, m = svc.shape
        n_pad = -(-n_ports // SUBLANES) * SUBLANES
        pad_m = (-m) % EVENT_BLOCK
        # event-major [m, B]; tail pad events come after every real one, so
        # the port state they disturb is never read back
        svc_t = jnp.pad(jnp.asarray(svc, jnp.float32).T,
                        ((0, pad_m), (0, (-b) % block_b)))
        dep = xbar_contend_padded(
            jnp.pad(jnp.asarray(dt, jnp.float32), (0, pad_m)),
            jnp.pad(jnp.asarray(src, jnp.int32), (0, pad_m)),
            jnp.pad(jnp.asarray(dst, jnp.int32), (0, pad_m)),
            svc_t,
            n_pad=n_pad, block_b=block_b, block_m=EVENT_BLOCK,
            interpret=interpret,
        )
        return dep[:m, :b].T
    if absolute:
        return xbar_contend_abs_ref(t, src, dst, svc, n_ports=n_ports)
    return xbar_contend_slack_ref(dt, src, dst, svc, n_ports=n_ports)
