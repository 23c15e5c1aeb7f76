"""MoE layer as a SPAC switching fabric (DESIGN.md §2.2).

Token dispatch to experts *is* an input-queued crossbar, and this layer
implements it with the paper's architecture mapped 1:1:

  forward table   router: ``learned_topk`` (FullLookup: direct indexed one-hot
                  lookup) or ``hash`` (MultiBankHash: k LSH banks; bank
                  conflicts surface as capacity overflows)
  VOQ buffer      per-(shard, expert) capacity buffers [E, C, d]; C sized by
                  the capacity factor — the DSE's statistical buffer sizing
                  (queue-occupancy histogram @ drop rate ε) tunes it
  scheduler       the all-to-all schedule: "single" (one bulk exchange),
                  "chunked:K" (K pipelined exchanges that overlap expert
                  compute — the iSLIP/EDRRM analogue)
  protocol        dispatch payload dtype: bf16 or int8+scales (quant_pack),
                  cutting fabric bytes ~2×
  drops           tokens past capacity are dropped (combine contributes 0),
                  reported in aux — the packet-loss column of Table II

The layer runs inside ``jax.shard_map`` over the full mesh: tokens are
batch-sharded over dp axes, each tensor-parallel shard takes a distinct slice
of its row's tokens (flat EP over the tp axis), packs VOQ buffers, exchanges
them with ``lax.all_to_all`` over tp, runs its local experts, and returns via
the reverse exchange + all-gather.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.kernels.quant_pack import ref as qref
from .config import ModelConfig, ShardingPlan
from .layers import dense_init

__all__ = ["MoEOptions", "init_moe", "apply_moe", "moe_in_specs"]


@dataclasses.dataclass(frozen=True)
class MoEOptions:
    """The DSE-tunable fabric knobs (CommSpec fragment)."""

    capacity_factor: float = 1.25
    payload: str = "bf16"          # bf16 | int8 — dispatch wire format
    a2a_chunks: int = 1            # 1 = "single"; >1 = pipelined chunks
    router: str = "learned_topk"   # learned_topk | hash
    weights: str = "gathered"      # gathered (FSDP all-gather at the boundary)
                                   # | ff_sharded (expert TP over the fsdp axis:
                                   #   zero weight comm — decode/serve path)

    @staticmethod
    def from_config(cfg: ModelConfig) -> "MoEOptions":
        return MoEOptions(capacity_factor=cfg.capacity_factor, router=cfg.router)


def init_moe(key, cfg: ModelConfig, plan: ShardingPlan):
    d, ff, e = cfg.d_model, cfg.d_ff, cfg.moe_experts
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    params = {
        "router": dense_init(k1, (d, e), dtype=jnp.float32),
        "hash_proj": jax.random.normal(k5, (d, 32), jnp.float32),  # LSH banks
        "w1": dense_init(k2, (e, d, ff)),
        "wg": dense_init(k3, (e, d, ff)),
        "w2": dense_init(k4, (e, ff, d), fan_in=ff),
    }
    fs = plan.fsdp_axes if plan.fsdp_weights else None
    fs = fs if fs is None or len(fs) > 1 else fs[0]
    tp = plan.tp_axis
    specs = {
        "router": P(None, None),
        "hash_proj": P(None, None),
        "w1": P(tp, fs, None),
        "wg": P(tp, fs, None),
        "w2": P(tp, None, fs),
    }
    return params, specs


def moe_in_specs(plan: ShardingPlan, weights: str = "gathered"):
    """shard_map in_specs for (x, params).

    gathered:   weights enter TP-sharded only (FSDP gather at the boundary).
    ff_sharded: weights additionally stay sharded on the expert-FFN dim over
                the first fsdp axis — no weight gather at all; the fabric
                computes partial FFNs and psums activations instead.
    """
    tp = plan.tp_axis
    if weights == "ff_sharded":
        fs = plan.fsdp_axes[0]
        return {
            "router": P(None, None),
            "hash_proj": P(None, None),
            "w1": P(tp, None, fs),
            "wg": P(tp, None, fs),
            "w2": P(tp, fs, None),
        }
    return {
        "router": P(None, None),
        "hash_proj": P(None, None),
        "w1": P(tp, None, None),
        "wg": P(tp, None, None),
        "w2": P(tp, None, None),
    }


def _route_learned(xs, router, topk):
    logits = jnp.einsum("td,de->te", xs.astype(jnp.float32), router)
    gates, experts = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), topk)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # load-balance aux loss (Switch-style): E * sum_e f_e * p_e
    e = router.shape[-1]
    probs_mean = jax.nn.softmax(logits, axis=-1).mean(0)
    counts = jnp.zeros((e,), jnp.float32).at[experts.reshape(-1)].add(1.0)
    f = counts / jnp.maximum(counts.sum(), 1.0)
    aux = e * jnp.sum(f * probs_mean)
    return experts.astype(jnp.int32), gates.astype(xs.dtype), aux


def _route_hash(xs, hash_proj, topk, e):
    """MultiBankHash routing: k independent sign-LSH banks; conflicts appear
    as load imbalance -> capacity overflow (the paper's bank-conflict cost)."""
    proj = jax.lax.stop_gradient(
        jnp.einsum("td,dh->th", xs.astype(jnp.float32), hash_proj))
    bits = (proj > 0).astype(jnp.uint32)
    # fold sign bits into k bank hashes (distinct odd multipliers per bank)
    mults = jnp.asarray([2654435761, 2246822519, 3266489917, 668265263,
                         374761393, 2869860233, 3624381081, 961748927][:topk], jnp.uint32)
    folded = jnp.sum(bits * (jnp.arange(bits.shape[-1], dtype=jnp.uint32) + 1), -1)
    experts = ((folded[:, None] + 1) * mults[None, :] >> jnp.uint32(8)) % jnp.uint32(e)
    gates = jnp.full(experts.shape, 1.0 / topk, xs.dtype)
    return experts.astype(jnp.int32), gates, jnp.zeros((), jnp.float32)


def _fabric(xs, params, cfg: ModelConfig, opts: MoEOptions, tp_axis: str,
            tp_size: int, ff_axis=None):
    """Per-device dispatch → exchange → expert FFN → return.  xs [T_m, d]."""
    t_m, d = xs.shape
    e, k = cfg.moe_experts, cfg.moe_topk
    e_loc = e // tp_size
    cap = max(int(np.ceil(t_m * k / e * opts.capacity_factor)), 1)

    if opts.router == "hash":
        experts, gates, aux = _route_hash(xs, params["hash_proj"], k, e)
    else:
        experts, gates, aux = _route_learned(xs, params["router"], k)

    # ---- VOQ pack: sort by expert, position-in-queue, drop past capacity
    e_flat = experts.reshape(-1)                                  # [T_m*k]
    g_flat = gates.reshape(-1)
    order = jnp.argsort(e_flat)                                   # stable
    es = e_flat[order]
    ts = order // k
    gs = g_flat[order]
    counts = jnp.bincount(es, length=e)                           # queue occupancy
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(es.shape[0], dtype=jnp.int32) - starts[es].astype(jnp.int32)
    keep = pos < cap
    slot = jnp.where(keep, es * cap + pos, 0)
    buf = jnp.zeros((e * cap, d), xs.dtype).at[slot].add(
        jnp.where(keep[:, None], xs[ts], 0))

    # ---- fabric exchange + expert compute, possibly in pipelined chunks
    n_chunks = max(1, min(opts.a2a_chunks, cap))
    c_sub = -(-cap // n_chunks)
    pad = n_chunks * c_sub - cap
    buf4 = buf.reshape(e, cap, d)
    if pad:
        buf4 = jnp.pad(buf4, ((0, 0), (0, pad), (0, 0)))
    buf5 = buf4.reshape(tp_size, e_loc, n_chunks, c_sub, d)

    w1, wg, w2 = params["w1"], params["wg"], params["w2"]         # local [E_loc,...]

    def expert_ffn(xin):                                          # [M, E_loc, c, d]
        h = jnp.einsum("mecd,edf->mecf", xin, w1)   # ff possibly ff/|ff_axis|
        g = jnp.einsum("mecd,edf->mecf", xin, wg)
        h = h * jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype)
        y = jnp.einsum("mecf,efd->mecd", h, w2)
        if ff_axis is not None:                     # combine partial FFN sums
            y = jax.lax.psum(y, ff_axis)
        return y

    outs = []
    for ci in range(n_chunks):                                    # pipelined exchanges
        send = buf5[:, :, ci]                                     # [M, E_loc, c_sub, d]
        if opts.payload == "int8":
            q, s = qref.quantize_ref(send.reshape(-1, d))
            q = jax.lax.all_to_all(q.reshape(tp_size, e_loc, c_sub, d), tp_axis, 0, 0)
            s = jax.lax.all_to_all(
                s.reshape(tp_size, e_loc, c_sub, d // qref.GROUP), tp_axis, 0, 0)
            recv = qref.dequantize_ref(
                q.reshape(-1, d), s.reshape(-1, d // qref.GROUP), xs.dtype
            ).reshape(tp_size, e_loc, c_sub, d)
        else:
            recv = jax.lax.all_to_all(send, tp_axis, 0, 0)
        y = expert_ffn(recv)
        if opts.payload == "int8":
            q, s = qref.quantize_ref(y.reshape(-1, d))
            q = jax.lax.all_to_all(q.reshape(tp_size, e_loc, c_sub, d), tp_axis, 0, 0)
            s = jax.lax.all_to_all(
                s.reshape(tp_size, e_loc, c_sub, d // qref.GROUP), tp_axis, 0, 0)
            y = qref.dequantize_ref(
                q.reshape(-1, d), s.reshape(-1, d // qref.GROUP), xs.dtype
            ).reshape(tp_size, e_loc, c_sub, d)
        else:
            y = jax.lax.all_to_all(y, tp_axis, 0, 0)
        outs.append(y)

    y5 = jnp.stack(outs, axis=2)                                  # [M, E_loc, K, c_sub, d]
    y_flat = y5.reshape(e, n_chunks * c_sub, d)[:, :cap].reshape(e * cap, d)

    # ---- VOQ combine: weighted un-dispatch (dropped tokens contribute 0)
    vals = y_flat[slot] * (gs * keep)[:, None]
    y_tok = jnp.zeros((t_m, d), xs.dtype).at[ts].add(vals)

    drop_frac = 1.0 - keep.mean()
    occupancy = counts                                            # per-queue depth sample
    return y_tok, aux, drop_frac, occupancy


def apply_moe(
    params,
    cfg: ModelConfig,
    plan: ShardingPlan,
    mesh,
    x: jnp.ndarray,                     # [B, S, d]
    opts: Optional[MoEOptions] = None,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    opts = opts or MoEOptions.from_config(cfg)
    tp = plan.tp_axis
    dp = tuple(plan.dp_axes)
    try:  # inside a pod-manual region, drop manual axes from the specs
        am = jax.sharding.get_abstract_mesh()
        manual = {n for n, t in zip(am.axis_names, am.axis_types)
                  if t == jax.sharding.AxisType.Manual}
        dp = tuple(a for a in dp if a not in manual)
    except Exception:
        pass
    tp_size = mesh.shape[tp]
    ff_axis = plan.fsdp_axes[0] if opts.weights == "ff_sharded" else None
    ff_size = mesh.shape[ff_axis] if ff_axis else 1

    def f(xl, prm):
        b_loc, s, d = xl.shape
        flat = xl.reshape(b_loc * s, d)
        if ff_axis:
            # expert-TP fabric: gather this row-group's tokens over the ff
            # axis (cheap at decode), compute partial FFNs on every shard,
            # psum the partials, then keep our slice — zero weight movement.
            row = jax.lax.axis_index(ff_axis)
            t_row = flat.shape[0]
            flat = jax.lax.all_gather(flat, ff_axis, axis=0, tiled=True)
        m_idx = jax.lax.axis_index(tp)
        t_loc = flat.shape[0]
        t_m = -(-t_loc // tp_size)                 # ceil: decode rows < tp_size
        pad = t_m * tp_size - t_loc
        if pad:
            flat = jnp.pad(flat, ((0, pad), (0, 0)))
        xs = jax.lax.dynamic_slice_in_dim(flat, m_idx * t_m, t_m, axis=0)
        y_m, aux, drops, occ = _fabric(xs, prm, cfg, opts, tp, tp_size,
                                       ff_axis=ff_axis)
        y = jax.lax.all_gather(y_m, tp, axis=0, tiled=True)       # [T_loc(+pad), d]
        if pad:
            y = y[:t_loc]
        if ff_axis:
            y = jax.lax.dynamic_slice_in_dim(y, row * t_row, t_row, axis=0)
        aux = jax.lax.pmean(aux, tp)
        drops = jax.lax.pmean(drops, tp)
        occ = jax.lax.psum(occ, tp)
        for ax in dp:
            aux = jax.lax.pmean(aux, ax)
            drops = jax.lax.pmean(drops, ax)
            occ = jax.lax.psum(occ, ax)
        return y.reshape(b_loc, s, d), aux, drops, occ

    in_specs = (P(dp, None, None), moe_in_specs(plan, opts.weights))
    out_specs = (P(dp, None, None), P(), P(), P())
    y, aux, drops, occ = jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False,
    )(x, {k: params[k] for k in ("router", "hash_proj", "w1", "wg", "w2")})
    return y, {"aux_loss": aux, "drop_frac": drops, "expert_load": occ}
