"""Greedy-crossbar contention scan, pure-jnp oracles (``jax.lax.scan``).

The stage-2 surrogate admits packets against input/output port availability
in global arrival order (``repro.sim.surrogate``).  This is the batched
reformulation: one shared, time-sorted trace and a batch axis of candidate
micro-architectures whose per-packet service times ``svc`` differ (bus
width, η, stalls, f_clk are all folded into ``svc`` upstream).

Two formulations, numerically equivalent, with different dtype trade-offs:

* ``xbar_contend_abs_ref`` carries *absolute* port-free times and returns
  absolute departure times — fewest ops per step and, in float64,
  bit-identical to the serial recurrence ``start = max(t_k, in_free_i,
  out_free_j); end = start + svc`` (returning ``end`` itself, not an offset,
  so downstream ulp-exact comparisons against arrival times hold).
* ``xbar_contend_slack_ref`` carries *slacks* (offsets from the current
  arrival instant) and returns departure *offsets*, so float32 keeps
  queueing-delay precision no matter how long the trace runs — the
  TPU-native form the Pallas kernel implements.

The float64 program no longer steps through these scans event by event:
``ops.xbar_contend_sweep`` solves the same recurrence by whole-trace
fixed-point sweeps.  Each event depends only on the last earlier event of
its input and of its output port, so the system is triangular with one
fixed point, the serial answer; the sweeps rise monotonically to it and stop
on a sweep that changes nothing, where every element is the same ``max``
and ``+`` of the same operands as here — bit-identical.  The absolute scan
stays the oracle, and the sweeps fall back to it on a trace that does not
settle within ``ops.SWEEP_CAP`` sweeps.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["xbar_contend_abs_ref", "xbar_contend_slack_ref"]


@functools.partial(jax.jit, static_argnames=("n_ports",))
def xbar_contend_abs_ref(
    t: jnp.ndarray,     # [m] or [m, B] float — earliest start per event
    src: jnp.ndarray,   # [m] int32 — source port per packet (shared trace)
    dst: jnp.ndarray,   # [m] int32 — destination port per packet
    svc: jnp.ndarray,   # [B, m] float — per-candidate service time per packet
    *,
    n_ports: int,
) -> jnp.ndarray:       # [B, m] — absolute departure time per packet
    b = svc.shape[0]
    zeros = jnp.zeros((b, n_ports), svc.dtype)

    def step(carry, x):
        in_f, out_f = carry
        tk, i, j, s = x
        start = jnp.maximum(jnp.maximum(in_f[:, i], out_f[:, j]), tk)
        end = start + s
        return (in_f.at[:, i].set(end), out_f.at[:, j].set(end)), end

    _, dep = jax.lax.scan(step, (zeros, zeros), (t, src, dst, svc.T))
    return dep.T


@functools.partial(jax.jit, static_argnames=("n_ports",))
def xbar_contend_slack_ref(
    dt: jnp.ndarray,    # [m] float — inter-arrival gaps, dt[0] == 0
    src: jnp.ndarray,   # [m] int32
    dst: jnp.ndarray,   # [m] int32
    svc: jnp.ndarray,   # [B, m] float
    *,
    n_ports: int,
) -> jnp.ndarray:       # [B, m] — departure offsets, as above
    b = svc.shape[0]
    zeros = jnp.zeros((b, n_ports), svc.dtype)

    def step(carry, x):
        in_s, out_s = carry
        dtk, i, j, s = x
        in_s = jnp.maximum(in_s - dtk, 0.0)
        out_s = jnp.maximum(out_s - dtk, 0.0)
        dep = jnp.maximum(in_s[:, i], out_s[:, j]) + s
        return (in_s.at[:, i].set(dep), out_s.at[:, j].set(dep)), dep

    _, dep = jax.lax.scan(step, (zeros, zeros), (dt, src, dst, svc.T))
    return dep.T
