"""Public batched crossbar-contention op.

Dispatch policy: float64 inputs take the absolute-time form, bit-identical
to the serial surrogate's recurrence — it returns *absolute* departure
times so ulp-exact occupancy comparisons hold downstream — computed by
whole-trace fixed-point sweeps (``xbar_contend_sweep``) rather than one
scan step per event; float32 inputs take the slack-form scan whose carries
never hold absolute timestamps, so precision survives long traces — that is
also the form the Pallas kernel implements for TPU deployment (validated in
interpret mode on CPU).  The f32 paths return departure *offsets*
(dep - arrival).

**The sweep form.**  Event k's departure is ``end_k = s_k + max(t0_k,
end[prev_in(k)], end[prev_out(k)])``, its predecessors being the last
earlier events on its input and its output port (none: 0, the scan's
initial carry).  Both predecessors come earlier, so the system is
triangular and has one fixed point, the serial answer.  A sweep applies the
recurrence to every event and candidate at once (two row gathers, a max and
an add on the event-major ``[m, B]`` block), starting from ``t0 + s``:

* the iterate rises monotonically toward the serial answer (``max`` and a
  correctly rounded ``+`` are monotone), and after d sweeps every event
  whose chain of binding predecessors is shorter than d holds its final
  value;
* a sweep that changes nothing has reached a fixed point, which is the
  serial one, and each of its elements comes from the same ``max`` and
  ``+`` on the same operands as the scan: the result is bit-identical.

So the sweeps needed are the longest busy chain plus one, a few on a switch
below line rate whatever the trace length.  A trace that has not settled
after ``SWEEP_CAP`` sweeps (a saturated port) runs the serial scan instead,
inside the same program, so the answer is exact on any trace and the worst
case is the scan plus ``SWEEP_CAP`` sweeps."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .kernel import xbar_contend_padded
from .ref import xbar_contend_abs_ref, xbar_contend_slack_ref

SUBLANES = 8     # the port axis of the tile's state pads to a multiple
EVENT_BLOCK = 1024   # events per grid step (the SMEM timeline block)
#: sweeps before the serial scan takes over.  On a TPU v5e one sweep costs
#: 1.9 scan steps at 2,048 events x 40 rows and 401 at 100,000 x 256, so
#: a trace that falls back after 32 sweeps costs at most 13% over the scan
#: alone at either size; registry traces settle in 3-8 (PERF.md, section 6)
SWEEP_CAP = 32


def _prev_on_port(port):
    """[m] int32: each event's last earlier event on the same port, or m
    (the zero row past the end) when it is the port's first.  A stable sort
    orders events by (port, index); the predecessor is the neighbour before
    within the same port."""
    m = port.shape[0]
    order = jnp.argsort(port, stable=True).astype(jnp.int32)
    g = port[order]
    i = jnp.arange(m)
    same = (i > 0) & (g == g[i - 1])
    return jnp.zeros(m, jnp.int32).at[order].set(
        jnp.where(same, order[i - 1], m))


def xbar_contend_sweep(t0, src, dst, svc_t, *, n_ports: int):
    """Absolute departures by fixed-point sweeps; see the module docstring.

    ``t0`` [m] or [m, B] earliest start per event (arrival, or arrival plus
    pipeline), ``src``/``dst`` [m] int32 ports, ``svc_t`` [m, B] service
    times, event-major.  Returns ``(end [m, B], sweeps, fell_back)``: the
    int32 scalars count the sweeps run and say whether the serial scan
    (``xbar_contend_abs_ref``) answered instead.  Jit-traceable; it runs
    inside its caller's program."""
    m = svc_t.shape[0]
    t0 = t0 if t0.ndim == 2 else t0[:, None]
    prev_in = _prev_on_port(src)
    prev_out = _prev_on_port(dst)
    zero_row = jnp.zeros((1, svc_t.shape[1]), svc_t.dtype)

    def again(c):
        _, sweeps, moved = c
        return moved & (sweeps < SWEEP_CAP)

    def sweep(c):
        ext, sweeps, _ = c
        end = ext[:m]
        new = jnp.maximum(jnp.maximum(ext[prev_in], ext[prev_out]), t0) + svc_t
        return (jax.lax.dynamic_update_slice(ext, new, (0, 0)), sweeps + 1,
                jnp.any(new != end))

    ext0 = jnp.concatenate([t0 + svc_t, zero_row])
    ext, sweeps, moved = jax.lax.while_loop(
        again, sweep, (ext0, jnp.int32(0), jnp.bool_(True)))
    end = jax.lax.cond(
        moved,
        lambda: xbar_contend_abs_ref(t0, src, dst, svc_t.T,
                                     n_ports=n_ports).T,
        lambda: ext[:m])
    return end, sweeps, moved.astype(jnp.int32)


def xbar_contend(t, dt, src, dst, svc, *, n_ports: int, use_pallas: bool = False,
                 block_b: int = 128, interpret: bool = False,
                 absolute: bool = None):
    """t/dt/src/dst [m] shared trace, svc [B, m] -> ``(dep, sweeps,
    fell_back)``: [B, m] departure times (absolute on the float64 path,
    arrival-relative offsets on float32) and the sweep counters of
    ``xbar_contend_sweep`` (both 0 on the scan paths, which sweep nothing).

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
        return dep[:m, :b].T, jnp.int32(0), jnp.int32(0)
    if absolute:
        end, sweeps, fell_back = xbar_contend_sweep(
            t, src, dst, jnp.asarray(svc).T, n_ports=n_ports)
        return end.T, sweeps, fell_back
    return (xbar_contend_slack_ref(dt, src, dst, svc, n_ports=n_ports),
            jnp.int32(0), jnp.int32(0))
