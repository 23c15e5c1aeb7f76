"""Comm-layer message protocols — SPAC's protocol customisation on the ICI.

The paper strips general-purpose header overhead per workload; here the
cross-pod gradient synchronisation protocol is customisable the same way:

  * ``bf16``  — baseline: plain all-reduce (GSPMD default behaviour)
  * ``int8``  — compressed protocol: per-128-group int8 payload + f32 scales,
                exchanged with an all-gather and averaged after dequantise
                (~3.5× fewer cross-pod bytes than a bf16 all-reduce)

``wrap_grad_fn_with_pod_protocol`` runs the whole grad computation inside a
shard_map that is *manual over the pod axis only* (data/model stay under
GSPMD), so the cross-pod exchange is exactly the collective we emit — the
dry-run HLO shows the byte reduction directly.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.quant_pack import ref as qref

__all__ = ["compressed_mean", "wrap_grad_fn_with_pod_protocol"]


def _leaf_compressed_mean(g: jnp.ndarray, axis: str) -> jnp.ndarray:
    """int8 all-gather + dequantised mean over a manual mesh axis."""
    shape, size = g.shape, g.size
    pad = (-size) % qref.GROUP
    flat = jnp.pad(g.reshape(-1).astype(jnp.float32), (0, pad)).reshape(-1, qref.GROUP)
    q, s = qref.quantize_ref(flat)
    qg = jax.lax.all_gather(q, axis)                    # [npod, R, G] int8 on the wire
    sg = jax.lax.all_gather(s, axis)                    # [npod, R, 1] f32 scales
    deq = qg.astype(jnp.float32) * sg                   # [npod, R, G]
    mean = deq.mean(0).reshape(-1)[: size].reshape(shape)
    return mean.astype(g.dtype)


def compressed_mean(grads, axis: str):
    return jax.tree.map(lambda g: _leaf_compressed_mean(g, axis), grads)


def wrap_grad_fn_with_pod_protocol(grad_fn: Callable, mesh, *, payload: str = "int8"):
    """grad_fn(params, batch) -> ((loss, metrics), grads), pod-synchronised
    with the chosen payload protocol."""
    def wrapped(params, batch):
        def inner(p, b):
            (loss, metrics), g = grad_fn(p, b)          # pod-local gradients
            if payload == "int8":
                g = compressed_mean(g, "pod")
            else:
                g = jax.tree.map(lambda x: jax.lax.pmean(x, "pod"), g)
            loss = jax.lax.pmean(loss, "pod")
            metrics = jax.tree.map(lambda m: jax.lax.pmean(m, "pod"), metrics)
            return (loss, metrics), g

        return jax.shard_map(
            inner, mesh=mesh, axis_names={"pod"},
            in_specs=(P(), P("pod")),
            out_specs=((P(), P()), P()),
            check_vma=False,
        )(params, batch)

    return wrapped
