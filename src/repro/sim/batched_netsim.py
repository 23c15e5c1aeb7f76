"""Batched stage-4 verifier (§IV-A.1 at fan-out scale).

``run_netsim`` replays the event-driven, finite-buffer switch model one
candidate at a time through a Python heapq loop — the DSE's wall-clock
bottleneck once stage 2 went batched (PR 1) and campaigns began fanning many
scenarios at once (PR 2).  This module reformulates that verifier as one
jitted sorted-arrival ``jax.lax.scan`` over the *shared* event timeline in
which every per-candidate parameter — bus width, η, pipeline/arbitration
cycles, ingress stalls, f_clk and, crucially, the stage-3 **sized VOQ
depths** — is a batch axis.

The finite-VOQ trick: departures inside a VOQ are FIFO (each admitted
packet's end time is ≥ its predecessor's, because both share the input and
output port), so "queue (i,j) holds ``depth`` undeparted packets at time t"
is equivalent to "the packet admitted ``depth`` admissions ago has not
departed by t".  A ``[B, N², D]`` ring buffer of departure times indexed by
admission count therefore answers the fullness check in O(1) — the slot an
admission is about to overwrite *is* the depth-ago packet — and the scan
needs no per-queue heaps, no draining, and no data-dependent inner loops
(which dominate wall-clock on CPU XLA; measured ~15x over the O(1) form).

``VOQKind.SHARED`` adds a global cap (``N·depth`` packets in flight across
the whole buffer) whose count is *not* FIFO across queues, so it is settled
exactly in a second, host-side pass: the scan runs unconstrained by the cap,
then for each shared candidate the in-flight timeline ``G(t_k) =
admitted-before-k − #(ends ≤ t_k)`` is reconstructed vectorially (one sort +
searchsorted).  If the cap was never reached at an admitted event, the
unconstrained run *is* the constrained run (the cap could never have fired)
and the batched result is exact; the rare candidates whose cap does bind
fall back to the serial heapq oracle — exact by definition, and flagged in
``meta["shared_cap_fallback"]`` so throughput reports stay honest.

The scan runs in float64 under a scoped ``enable_x64`` and shares
``service_times`` / ``switch_arrival_times`` with the serial path, so
admission decisions, drop counts and departure times are bit-identical to
``run_netsim`` (``tests/test_batched_netsim.py`` asserts it per candidate).

Plain ``jax.lax`` rather than Pallas: the verifier is an irregular
gather/scatter state machine whose contract is float64 exactness against the
serial oracle — the opposite of the f32 tile-parallel shape Pallas rewards
(the stage-2 engine keeps a Pallas crossbar for that; see
``kernels/xbar/kernel.py``).

Retransmission (driver ARQ) inserts events dynamically and stays on the
serial path: ``run_netsim_batched`` raises ``NotImplementedError`` for
retransmitting configs so callers fall back honestly instead of silently
diverging.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.retrace import track
from repro.analysis.spans import note, span
from repro.core.archspec import SwitchArch, VOQKind
from repro.core.binding import BoundProtocol
from repro.core.dse import VerifyResult
from repro.kernels.netsim import netsim_fixed_point, resolve_use_kernel

from .backannotate import HardwareParams, annotate
from .netsim import NetSimConfig, run_netsim, service_times
from .timeline import stage4_timeline

__all__ = ["run_netsim_batched"]


def _verify_engine_impl(now, src, dst, svc, pipe, depth, mod, *, n_ports,
                        d_max):
    """One call: the finite-VOQ admission scan for a whole batch.

    Carries ``in_free``/``out_free`` [B, N] port availability, the [B, N², D]
    departure-time ring and the [B, N²] admission counters.  ``mod`` is the
    per-candidate ring modulus (``min(depth, m)`` — a queue can never hold
    more than the whole trace), so one static ``d_max`` serves mixed-depth
    batches.  Returns per-event departure times and admission flags; drop
    counts and latencies reduce on the host.  All carries are per-candidate
    (the timeline is replicated), so sharding the candidate axis with
    ``shard_map`` reproduces the monolithic scan bit-for-bit."""
    b_n = svc.shape[1]
    q_n = n_ports * n_ports
    brange = jnp.arange(b_n)

    def step(carry, xs):
        in_free, out_free, ring, tail = carry
        t_now, i, j, s = xs
        q = i * n_ports + j
        tq = tail[:, q]
        # the slot this admission would overwrite holds the departure time of
        # the packet `depth` admissions ago — FIFO order makes "that packet
        # has not left by now" ⟺ "the queue holds depth undeparted packets"
        oldest = ring[brange, q, tq % mod]
        full = (tq >= depth) & (oldest > t_now)
        admit = ~full
        start = jnp.maximum(jnp.maximum(t_now + pipe, in_free[:, i]),
                            out_free[:, j])
        end = start + s
        in_free = in_free.at[:, i].set(jnp.where(admit, end, in_free[:, i]))
        out_free = out_free.at[:, j].set(jnp.where(admit, end, out_free[:, j]))
        ring = ring.at[brange, q, tq % mod].set(jnp.where(admit, end, oldest))
        tail = tail.at[:, q].add(admit.astype(tail.dtype))
        return (in_free, out_free, ring, tail), (end, admit)

    ports0 = jnp.zeros((b_n, n_ports), svc.dtype)
    init = (ports0, ports0, jnp.zeros((b_n, q_n, d_max), svc.dtype),
            jnp.zeros((b_n, q_n), jnp.int32))
    _, (end, admit) = jax.lax.scan(step, init, (now, src, dst, svc))
    return end.T, admit.T                                  # [B, m] each


_verify_engine = track("netsim.engine",
                       jax.jit(_verify_engine_impl,
                               static_argnames=("n_ports", "d_max")))


@functools.lru_cache(maxsize=None)
def _sharded_verify_engine(mesh, n_ports, d_max):
    """The same admission scan, candidate axis sharded over the mesh.

    ``svc`` arrives [m, B] (scan xs layout) so its candidate axis is axis 1;
    ``pipe``/``depth``/``mod`` split along axis 0; the event timeline
    (``now``/``src``/``dst``) is replicated.  Rowwise-independent carries —
    no collectives — so each shard is bitwise the serial recurrence on its
    slice."""
    from jax.sharding import PartitionSpec as P

    names = tuple(mesh.axis_names)
    cand = P(names)
    rep = P()
    body = functools.partial(_verify_engine_impl, n_ports=n_ports,
                             d_max=d_max)
    name = (f"netsim.sharded[{'x'.join(map(str, mesh.devices.shape))} "
            f"{','.join(names)} n_ports={n_ports} d_max={d_max}]")
    return track(name, jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(rep, rep, rep, P(None, names), cand, cand, cand),
        out_specs=(cand, cand), check_vma=False)))


def _shared_cap_ok(admit_b: np.ndarray, sorted_ends_b: np.ndarray,
                   now: np.ndarray, cap: int) -> bool:
    """True iff the shared-buffer cap never binds in the unconstrained run.

    ``G(t_k) = admitted-before-k − #(admitted ends ≤ t_k)`` is the exact
    in-flight count the serial path's shared heap sees at event k (later
    admissions end strictly after t_k, so counting departures over *all*
    admitted ends is safe).  If G < cap at every per-queue-admitted event,
    the cap could never have dropped a packet and the unconstrained dynamics
    are the true dynamics.

    ``sorted_ends_b`` is the candidate's ascending departure times with
    dropped events mapped to +inf — sorted once for the whole batch by the
    caller (one ``np.sort(where(admit, end, inf), axis=1)``) instead of a
    fresh per-candidate ``np.sort`` inside the loop; the inf tail never
    lands left of a finite ``now``, so ``side="right"`` counts are
    unchanged."""
    g_before = np.cumsum(admit_b) - admit_b
    departed = np.searchsorted(sorted_ends_b, now, side="right")
    return not bool(np.any(admit_b & (g_before - departed >= cap)))


def _sorted_admitted_ends(end: np.ndarray, admit: np.ndarray,
                         rows: Sequence[int]) -> Dict[int, np.ndarray]:
    """Batched replacement for the per-candidate ``np.sort`` the shared-cap
    check used to do: one sort over the selected rows, dropped events pushed
    to +inf so every row shares one [len(rows), m] sort."""
    if not rows:
        return {}
    idx = np.asarray(rows)
    sorted_ends = np.sort(np.where(admit[idx], end[idx], np.inf), axis=1)
    return {int(b): sorted_ends[i] for i, b in enumerate(idx)}


def _empty_result(hw: HardwareParams) -> VerifyResult:
    return VerifyResult(
        p99_latency_ns=math.inf, mean_latency_ns=math.inf, drop_rate=0.0,
        throughput_gbps=0.0,
        meta={"latency_ns": np.zeros(0), "latency_full_ns": np.zeros(0),
              "delivered": 0, "offered": 0,
              "hw": hw, "engine": "batched_netsim"})


def _run_group(archs, bounds, trace, hw_list, cfg,
               mesh_spec=None) -> List[VerifyResult]:
    """All candidates share n_ports *and* header wire-bytes; every other
    parameter is a batch axis.  The header width is structural here — unlike
    stage 2, the event timeline (host-NIC serialisation) depends on wire
    size, so mixed-header co-design batches are partitioned by
    ``header_bytes`` upstream and each partition shares one timeline."""
    n = archs[0].n_ports
    with span("spac.stage4.timeline"):
        tl4 = stage4_timeline(trace, n, bounds[0].header_bytes,
                              cfg.prop_delay_s)
    t0 = tl4.t0
    m = t0.size
    wire = tl4.wire
    link_bps = trace.link_gbps * 1e9
    b_n = len(archs)
    if m == 0:
        return [_empty_result(hw) for hw in hw_list]

    svc = np.empty((b_n, m), np.float64)
    pipe = np.empty(b_n, np.float64)
    depth = np.empty(b_n, np.int64)
    with span("spac.stage4.prepare"):
        for b, (arch, hw) in enumerate(zip(archs, hw_list)):
            svc[b], pipe[b] = service_times(arch, hw, wire, link_bps)
            depth[b] = arch.voq_depth

    order = tl4.order                          # == the heap's (time, pkt) order
    now = tl4.now
    # ring modulus: a queue never holds more than min(depth, m) packets; the
    # static ring size rounds up to a power of two so sweeps with nearby sized
    # depths reuse one compiled scan
    mod = np.minimum(np.maximum(depth, 1), m).astype(np.int32)
    # d_max comes from the *unpadded* depths (pad rows replicate row 0), so
    # the compiled ring size — and the scan it keys — is mesh-invariant
    d_max = 1 << int(int(mod.max()) - 1).bit_length()

    # the candidate axis pads to its bucket width (replicas of row 0,
    # stripped on the fetch), a multiple of the shard count under a mesh
    from repro.launch.mesh import bucket_pad
    k = 1 if mesh_spec is None else mesh_spec.shard_axis
    engine = (_sharded_verify_engine(mesh_spec.build(), n, d_max) if k > 1
              else _verify_engine)
    svc_p = bucket_pad(svc, k)
    args = (now, tl4.src_o.astype(np.int32), tl4.dst_o.astype(np.int32),
            svc_p[:, order].T, bucket_pad(pipe, k),
            bucket_pad(depth, k).astype(np.int32), bucket_pad(mod, k))
    with span("spac.stage4.scan", jit=engine, rows=b_n,
              pad_rows=svc_p.shape[0] - b_n):
        with jax.enable_x64():
            end, admit = (engine(*args) if k > 1
                          else engine(*args, n_ports=n, d_max=d_max))
        end = np.asarray(end, np.float64)[:b_n]  # strip pad rows
        admit = np.asarray(admit, bool)[:b_n]

    with span("spac.stage4.reduce"):
        # one batched sort replaces the per-candidate np.sort the shared-cap
        # check used to run inside the loop below
        sorted_ends = _sorted_admitted_ends(
            end, admit,
            [b for b in range(b_n)
             if archs[b].voq is VOQKind.SHARED and int(depth[b]) >= 1])
        out: List[VerifyResult] = []
        n_fall = 0
        for b, (arch, bound, hw) in enumerate(zip(archs, bounds, hw_list)):
            fallback = None
            if int(depth[b]) < 1:
                # degenerate depth<=0: serial semantics drop every packet; the
                # scan's ring check can't express an always-full queue
                fallback = "degenerate_depth"
            elif arch.voq is VOQKind.SHARED and not _shared_cap_ok(
                    admit[b], sorted_ends[b], now, n * int(depth[b])):
                # the global cap binds for this candidate: the per-queue-only
                # scan diverges
                fallback = "shared_cap"
            if fallback is not None:
                # replay through the exact serial oracle, flagged for honesty
                n_fall += 1
                with span("spac.stage4.fallback", reason=fallback):
                    v = run_netsim(arch, bound, trace, hw=hw, cfg=cfg)
                v.meta["shared_cap_fallback"] = fallback == "shared_cap"
                v.meta["fallback"] = fallback
                out.append(v)
                continue
            out.append(_metrics_result(end[b], admit[b], order, t0,
                                       tl4.wire_e, tl4.t0_min, cfg, hw, m))
    note(fallback_rows=n_fall)
    return out


def _metrics_result(end_b, admit_b, order, t0, wire_e, t0_min, cfg, hw,
                    m) -> VerifyResult:
    """Reduce one candidate's (end, admit) to a VerifyResult; both paths
    reduce through it, so kernel-path results are bit-identical."""
    latency = np.full(m, np.nan)
    latency[order] = np.where(
        admit_b, (end_b + cfg.prop_delay_s - t0[order]) * 1e9, np.nan)
    done = ~np.isnan(latency)
    lat = latency[done]
    t_end = float(np.max(end_b, where=admit_b, initial=0.0))
    delivered_bits = float(int(wire_e[admit_b].sum()) * 8)
    duration = max(t_end - t0_min, 1e-12)
    return VerifyResult(
        p99_latency_ns=float(np.percentile(lat, 99)) if lat.size else math.inf,
        mean_latency_ns=float(lat.mean()) if lat.size else math.inf,
        drop_rate=int((~admit_b).sum()) / max(m, 1),
        throughput_gbps=delivered_bits / duration / 1e9,
        meta={"latency_ns": lat, "latency_full_ns": latency,
              "delivered": int(done.sum()),
              "offered": int(m), "hw": hw, "engine": "batched_netsim"},
    )


def _run_group_kernel(archs, bounds, trace, hw_list, cfg,
                      mesh_spec=None) -> List[VerifyResult]:
    """The segmented-kernel twin of ``_run_group``.

    Replaces the [B, N², D] ring scan with the speculative fixed point
    (``kernels.netsim.netsim_fixed_point``): one lean port replay fused with
    a segmented all-admitted fullness check settles the whole batch in a
    single round when stage-3 sizing holds, and only dropping rows iterate.
    Identical dynamics rows — NSGA-II batches repeat genomes — collapse to
    one scan row and fan back out afterwards.  Departure times, admission
    flags and every reduced metric are bit-identical to the default path
    (same float64 arithmetic in the same order); rows the fixed point cannot
    settle exactly (degenerate depth, binding shared cap, no convergence)
    take the serial oracle, flagged in ``meta`` exactly like the default
    path's fallbacks."""
    n = archs[0].n_ports
    with span("spac.stage4.timeline"):
        tl4 = stage4_timeline(trace, n, bounds[0].header_bytes,
                              cfg.prop_delay_s)
    m = tl4.now.size
    b_n = len(archs)
    if m == 0:
        return [_empty_result(hw) for hw in hw_list]
    link_bps = trace.link_gbps * 1e9
    order, now, t0 = tl4.order, tl4.now, tl4.t0

    svc_e = np.empty((b_n, m), np.float64)      # event order (pre-permuted)
    pipe = np.empty(b_n, np.float64)
    depth = np.empty(b_n, np.int64)
    out: List[Optional[VerifyResult]] = [None] * b_n
    fall: Dict[int, str] = {}
    # candidate dedup: rows with identical (service times, pipe, depth, VOQ
    # kind) have identical dynamics — one scan row serves them all
    slot_of: Dict[Tuple, int] = {}
    uniq_rows: List[int] = []
    rep = np.full(b_n, -1, np.int64)
    with span("spac.stage4.prepare"):
        for b, (arch, hw) in enumerate(zip(archs, hw_list)):
            s, pipe[b] = service_times(arch, hw, tl4.wire, link_bps)
            svc_e[b] = s[order]
            depth[b] = arch.voq_depth
        for b in range(b_n):
            if int(depth[b]) < 1:
                fall[b] = "degenerate_depth"
                continue
            key = (svc_e[b].tobytes(), float(pipe[b]), int(depth[b]),
                   archs[b].voq is VOQKind.SHARED)
            slot = slot_of.setdefault(key, len(uniq_rows))
            if slot == len(uniq_rows):
                uniq_rows.append(b)
            rep[b] = slot

    uniq_res: List[Optional[VerifyResult]] = []
    rounds = 0
    if uniq_rows:
        ui = np.asarray(uniq_rows)
        with jax.enable_x64():
            end, admit, conv, rounds = netsim_fixed_point(
                now, tl4.src_o.astype(np.int32), tl4.dst_o.astype(np.int32),
                svc_e[ui], pipe[ui], depth[ui], n_ports=n, chain=tl4.chain,
                mesh_spec=mesh_spec)
        with span("spac.stage4.reduce"):
            sorted_ends = _sorted_admitted_ends(
                end, admit,
                [i for i, b in enumerate(uniq_rows)
                 if archs[b].voq is VOQKind.SHARED and bool(conv[i])])
            for i, b in enumerate(uniq_rows):
                if not bool(conv[i]):
                    uniq_res.append(None)
                    fall[b] = "kernel_unconverged"
                    continue
                if archs[b].voq is VOQKind.SHARED and not _shared_cap_ok(
                        admit[i], sorted_ends[i], now, n * int(depth[b])):
                    uniq_res.append(None)
                    fall[b] = "shared_cap"
                    continue
                uniq_res.append(_metrics_result(
                    end[i], admit[i], order, t0, tl4.wire_e, tl4.t0_min, cfg,
                    hw_list[b], m))

    n_fall = 0
    for b in range(b_n):
        slot = int(rep[b])
        if slot >= 0 and uniq_res[slot] is not None:
            v = uniq_res[slot]
            # duplicates share the (read-only by convention) arrays but get
            # fresh meta dicts — callers annotate meta in place
            out[b] = dataclasses.replace(
                v, meta={**v.meta, "hw": hw_list[b]})
        else:
            # the fixed point defers to the serial oracle, flagged exactly
            # like the default path
            fb = fall.get(b) or fall.get(uniq_rows[slot], "kernel_unconverged")
            n_fall += 1
            with span("spac.stage4.fallback", reason=fb):
                v = run_netsim(archs[b], bounds[b], trace, hw=hw_list[b],
                               cfg=cfg)
            v.meta["shared_cap_fallback"] = fb == "shared_cap"
            v.meta["fallback"] = fb
            out[b] = v
    note(unique_rows=len(uniq_rows), rounds=rounds, fallback_rows=n_fall)
    return out


def run_netsim_batched(
    archs: Sequence[SwitchArch],
    bound: Union[BoundProtocol, Sequence[BoundProtocol]],
    trace,
    *,
    hw: Optional[Sequence[HardwareParams]] = None,
    cfg: Optional[NetSimConfig] = None,
    back_annotation: bool = True,
    i_burst: float = 1.0,
    mesh=None,
    use_kernel=False,
) -> List[VerifyResult]:
    """Verify a whole sized-candidate batch against one shared trace.

    ``mesh`` is an optional ``repro.launch.mesh.MeshSpec``: more than one
    shard pads the candidate axis to the mesh extent and runs the admission
    scan under ``shard_map``, bit-identical to the serial default
    (``mesh=None``, byte-identical path).

    Results are index-aligned with ``archs`` and, candidate by candidate,
    bit-identical to ``run_netsim`` (same drop counts, same delivered set,
    same latency array).  ``bound`` is one ``BoundProtocol`` or a per-
    candidate sequence (the co-design DSE's mixed header widths); candidates
    may mix every architectural policy and any sized VOQ depth.  ``n_ports``
    and header wire-bytes are structural (the event timeline depends on
    both), so mixed batches are partitioned internally by
    ``(n_ports, header_bytes)`` and stitched back in input order.

    Memory: the scan carries a ``[B, N², min(max_depth, m)]`` float64 ring of
    departure times — ~34 MB for 64 candidates at 8 ports and depth 1024;
    chunk very large sweeps into multiple calls.

    ``use_kernel`` selects the segmented-kernel engine (``"auto"``/``"on"``/
    ``"off"`` or a bool; auto = on unless ``SPAC_NETSIM_KERNEL=off``): the
    speculative fixed point of ``repro.kernels.netsim`` replaces the ring
    scan, bit-identical per candidate, several times faster on sized sweeps
    (see ``benchmarks/netsim_kernel.py``).  The default stays the ring-scan
    path byte-for-byte.
    """
    if cfg is None:
        cfg = NetSimConfig()
    from repro.launch.mesh import MeshSpec
    mesh = MeshSpec.coerce(mesh)
    if mesh is not None and mesh.is_single():
        mesh = None
    archs = list(archs)
    bounds = (list(bound) if isinstance(bound, (list, tuple))
              else [bound] * len(archs))
    if len(bounds) != len(archs):
        raise ValueError(f"bound has {len(bounds)} entries for {len(archs)} "
                         "archs; they must be index-aligned")
    if cfg.retransmit and any(b.has("seq_no") for b in bounds):
        raise NotImplementedError(
            "driver-level retransmission inserts events dynamically; "
            "fall back to the serial run_netsim for retransmitting configs")
    if not archs:
        return []
    if hw is None:
        source = "cycle_sim" if back_annotation else "model"
        hw = [annotate(a, b, source=source, i_burst=i_burst)
              for a, b in zip(archs, bounds)]
    hw = list(hw)
    if len(hw) != len(archs):
        raise ValueError(f"hw has {len(hw)} entries for {len(archs)} archs; "
                         "they must be index-aligned")

    runner = (_run_group_kernel if resolve_use_kernel(use_kernel)
              else _run_group)
    groups: Dict[Tuple[int, int], List[int]] = {}
    for i, a in enumerate(archs):
        groups.setdefault((a.n_ports, bounds[i].header_bytes), []).append(i)
    if len(groups) == 1:
        return runner(archs, bounds, trace, hw, cfg, mesh_spec=mesh)
    out: List[Optional[VerifyResult]] = [None] * len(archs)
    for idx in groups.values():
        part = runner([archs[i] for i in idx], [bounds[i] for i in idx],
                      trace, [hw[i] for i in idx], cfg, mesh_spec=mesh)
        for i, v in zip(idx, part):
            out[i] = v
    return out
