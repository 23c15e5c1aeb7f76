"""Batched stage-2 surrogate engine (§IV-A.2 at fan-out scale).

``run_surrogate`` evaluates one ``(arch, depths)`` candidate per call with a
Python-loop crossbar — fine for a handful of candidates, hopeless for the
thousands Algorithm 1's stage 2 wants to screen.  This module reformulates
the event-driven transaction model as a *sorted-arrival scan over a shared
trace* in which every per-candidate parameter (bus width, pipeline depth, η,
ingress stalls, f_clk) is a batch axis:

  * the greedy-crossbar recurrence runs in one jitted call over the whole
    batch (``repro.kernels.xbar``): on the float64 path as whole-trace
    fixed-point sweeps, bit-identical to the serial scan they replace; on
    float32 as a scan with ``[B, n_ports]`` slack carries (or the optional
    Pallas kernel),
  * per-candidate departure offsets and sustained throughput come out of the
    same jitted call; latency (one broadcast) and its quantiles reduce on
    the host (numpy's sort beats XLA's CPU sort on the [B, m] matrix by
    ~10x, measured, and shipping a second [B, m] matrix off-device would
    double the transfer),
  * exact per-VOQ occupancy counting (PASTA sampling) is done once on the
    host from the batched departure times by
    ``repro.kernels.netsim.segmented_occupancy``: exact comparisons and
    integer keys, equal to the serial path's counts by construction, so
    stage-3 sizing and drop counts cannot drift.

Precision: with ``precision="float64"`` (default) the scan runs under a
scoped ``jax.enable_x64`` so departure times match the serial
float64 model exactly; ``precision="float32"`` keeps TPU-native dtypes (the
scan carries arrival-relative *slacks*, never absolute timestamps, so f32
still holds queueing-delay precision on arbitrarily long traces).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.retrace import track
from repro.analysis.spans import note, span
from repro.core.archspec import SwitchArch, VOQKind
from repro.core.binding import BoundProtocol
from repro.core.dse import SurrogateResult

from .backannotate import HardwareParams, annotate
from .timeline import stage2_timeline
from repro.kernels.netsim import segmented_occupancy
from repro.kernels.xbar import xbar_contend

__all__ = ["BatchedSurrogateResult", "run_surrogate_batched", "DEFAULT_QUANTILES"]

DEFAULT_QUANTILES = (50.0, 90.0, 99.0)


def _engine_impl(dt, src, dst, svc, t, wire_bits, *, n_ports, use_pallas,
                 interpret):
    """One call: contention (fixed-point sweeps on the float64 path, a scan
    otherwise) + throughput, and the sweep counters ``(sweeps, fell_back)``.

    Latency (one broadcast over dep) and quantile reduction deliberately
    stay on the host: returning the [B, m] latency matrix would double the
    largest device-to-host transfer, and XLA's CPU sort is ~10x slower than
    numpy's (measured).  The scan is rowwise over the candidate axis (per-
    candidate carries, replicated timeline), so any partition of the batch —
    including a shard_map split across devices — is bitwise-identical to the
    monolithic call."""
    dep, sweeps, fell_back = xbar_contend(t, dt, src, dst, svc,
                                          n_ports=n_ports,
                                          use_pallas=use_pallas,
                                          interpret=interpret)
    # dep is absolute on the f64 path, an arrival-relative offset on f32
    absolute = dep.dtype == jnp.float64 and not use_pallas
    dep_end = dep if absolute else t[None, :] + dep
    duration = jnp.maximum(jnp.max(dep_end, axis=1), 1e-12)
    thru = wire_bits / duration / 1e9                           # [B] Gbps
    return dep, thru, sweeps, fell_back


_engine = track("surrogate.engine",
                jax.jit(_engine_impl,
                        static_argnames=("n_ports", "use_pallas",
                                         "interpret")))


@functools.lru_cache(maxsize=None)
def _sharded_engine(mesh, n_ports, use_pallas, interpret):
    """The same scan, candidate axis sharded over every mesh axis.

    ``svc`` [B, m] and ``wire_bits`` [B] split along B; the timeline
    (``dt``/``src``/``dst``/``t``) is replicated.  No collectives: rows are
    independent, so each shard runs the serial recurrence on its slice and
    the result is bitwise-identical to the single-device call.  Each shard
    returns its own sweep counters, one per shard."""
    from jax.sharding import PartitionSpec as P

    cand = P(tuple(mesh.axis_names))
    rep = P()

    def body(*args):
        dep, thru, sweeps, fell_back = _engine_impl(
            *args, n_ports=n_ports, use_pallas=use_pallas, interpret=interpret)
        return dep, thru, sweeps[None], fell_back[None]

    name = (f"surrogate.sharded[{'x'.join(map(str, mesh.devices.shape))} "
            f"{','.join(mesh.axis_names)} n_ports={n_ports}]")
    return track(name, jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(rep, rep, rep, cand, rep, cand),
        out_specs=(cand,) * 4, check_vma=False)))


@dataclasses.dataclass
class BatchedSurrogateResult:
    """Stage-2 fan-out output: [B, ...] arrays over the candidate batch."""

    archs: List[SwitchArch]
    hw: List[HardwareParams]
    latency_ns: np.ndarray         # [B, m] per-packet latency
    quantiles: np.ndarray          # [B, nq] latency quantiles (ns)
    quantile_qs: Sequence[float]   # the nq percentile points
    throughput_gbps: np.ndarray    # [B]
    q_occupancy: np.ndarray        # [B, m] exact per-VOQ occupancy samples
    dep_end_s: np.ndarray          # [B, m] absolute departure times
    t_s: np.ndarray                # [m] shared arrival times (t[0] == 0)
    line_rate_feasible: np.ndarray  # [B] bool
    meta: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def peak_occupancy(self) -> np.ndarray:
        """[B] — the BRAM lower bound the paper reads off stage 2."""
        return self.q_occupancy.max(axis=1, initial=0)

    def occupancy_hist(self) -> np.ndarray:
        """[B, peak+1] histogram of occupancy samples (shared bin edges)."""
        b, m = self.q_occupancy.shape
        occ = np.maximum(self.q_occupancy, 0)
        width = int(occ.max(initial=0)) + 1
        flat = (np.arange(b)[:, None] * width + occ).reshape(-1)
        return np.bincount(flat, minlength=b * width).reshape(b, width)

    def results(self) -> List[SurrogateResult]:
        """Materialise per-candidate ``SurrogateResult``s (serial-compatible)."""
        out = []
        shared_rows = [b for b, a in enumerate(self.archs)
                       if a.voq is VOQKind.SHARED]
        if shared_rows:
            # sort all shared rows at once instead of one np.sort per
            # candidate inside the loop below
            sorted_dep = np.sort(self.dep_end_s[shared_rows], axis=1)
            sorted_of = {b: sorted_dep[i] for i, b in enumerate(shared_rows)}
        for b, (arch, hw) in enumerate(zip(self.archs, self.hw)):
            if arch.voq is VOQKind.SHARED:
                m = self.t_s.size
                departed = np.searchsorted(sorted_of[b], self.t_s,
                                           side="right")
                shared_occ = np.arange(m) - departed
            else:
                shared_occ = None
            out.append(SurrogateResult(
                q_occupancy=self.q_occupancy[b].astype(np.float64),
                latency_ns=self.latency_ns[b],
                throughput_gbps=float(self.throughput_gbps[b]),
                meta={
                    "hw": hw,
                    "shared_occupancy": shared_occ,
                    "q_occ_max": int(self.q_occupancy[b].max(initial=0)),
                    "line_rate_feasible": bool(self.line_rate_feasible[b]),
                    "batched": True,
                },
            ))
        return out


def _run_group(archs, bounds, trace, hw_list, use_pallas, interpret, precision,
               quantiles, mesh_spec=None):
    """All candidates share n_ports; every other parameter — including the
    protocol's header wire-bytes under co-design — is a batch axis.  The
    shared arrival timeline is the trace's (candidate-independent), so mixed
    header widths still ride one jitted scan: the header only reshapes the
    per-candidate service times and delivered wire bits."""
    n = archs[0].n_ports
    with span("spac.stage2.timeline"):
        tl2 = stage2_timeline(trace, n)
    t, src, dst, payload = tl2.t, tl2.src, tl2.dst, tl2.payload
    m = t.size

    b_n = len(archs)
    svc = np.empty((b_n, m), np.float64)
    pipe_s = np.empty(b_n, np.float64)
    feasible = np.empty(b_n, bool)
    wire_bits = np.empty(b_n, np.float64)
    # one wire-size array per distinct header width: classic shared-bound
    # batches pay for it once, co-design pays once per layout width
    wire_cache: Dict[int, Any] = {}
    with span("spac.stage2.prepare"):
        for b, (arch, bound, hw) in enumerate(zip(archs, bounds, hw_list)):
            cached = wire_cache.get(bound.header_bytes)
            if cached is None:
                wb = payload + bound.header_bytes
                cached = (wb, float(wb.sum() * 8))
                wire_cache[bound.header_bytes] = cached
            wire_bytes, wire_bits[b] = cached
            flit_bytes = arch.bus_bits // 8
            size_flits = np.maximum(1, -(-wire_bytes // flit_bytes))
            svc[b] = (size_flits + hw.ingress_stall_cycles) / (hw.fclk_hz * hw.eta)
            pipe_s[b] = (hw.pipeline_cycles + hw.arb_cycles) / hw.fclk_hz
            feasible[b] = bool(m == 0 or svc[b].mean() * hw.fclk_hz
                               <= arch.ii * size_flits.mean() * 1.25)

    dtype = np.float64 if precision == "float64" else np.float32
    if m == 0:
        dep = np.zeros((b_n, 0))
        thru = np.zeros(b_n)
    else:
        dt = tl2.dt
        # pad the candidate axis to its bucket width (throwaway replicas of
        # row 0, stripped below) so widths share compiled programs; with a
        # mesh it is also a multiple of the shard count, split over every
        # mesh axis
        from repro.launch.mesh import bucket_pad
        k = 1 if mesh_spec is None else mesh_spec.shard_axis
        args = (dt.astype(dtype), src.astype(np.int32), dst.astype(np.int32),
                bucket_pad(svc.astype(dtype), k), t.astype(dtype),
                bucket_pad(wire_bits.astype(dtype), k))
        if k > 1:
            engine = jitted = _sharded_engine(mesh_spec.build(), n, use_pallas,
                                              interpret)
        else:
            jitted = _engine
            engine = functools.partial(_engine, n_ports=n,
                                       use_pallas=use_pallas,
                                       interpret=interpret)
        # the device call: copies in, the sweeps, and the fetch back
        with span("spac.stage2.scan", jit=jitted, rows=b_n,
                  pad_rows=args[3].shape[0] - b_n):
            if precision == "float64":
                with jax.enable_x64():
                    dep, thru, sweeps, fell_back = jax.device_get(
                        engine(*args))
                # the slowest shard's sweeps; whether any shard fell back
                note(sweeps=int(np.max(sweeps)),
                     scan_fallback=int(np.max(fell_back)))
            else:
                dep, thru, _, _ = engine(*args)
                dep, thru = (np.asarray(dep, np.float64),
                             np.asarray(thru, np.float64))
        dep, thru = dep[:b_n], thru[:b_n]       # strip pad rows
    with span("spac.stage2.reduce"):
        if precision == "float64":
            # the f64 scan returns absolute departure times so the occupancy
            # comparisons below see the serial path's exact values (no offset
            # round-trip); latency then subtracts t exactly as the serial model
            dep_end = np.asarray(dep, np.float64)
            lat = (dep_end - t[None, :] + pipe_s[:, None]) * 1e9
        else:
            dep_end = t[None, :] + np.asarray(dep, np.float64)
            lat = (dep + pipe_s[:, None]) * 1e9
        quant = (np.percentile(lat, quantiles, axis=1).T if m
                 else np.zeros((b_n, len(quantiles))))
        with span("spac.stage2.occupancy", rows=b_n, events=m):
            occupancy = segmented_occupancy(t, dep_end, tl2.chain)
    return BatchedSurrogateResult(
        archs=list(archs), hw=list(hw_list),
        latency_ns=np.asarray(lat, np.float64),
        quantiles=np.asarray(quant, np.float64), quantile_qs=tuple(quantiles),
        throughput_gbps=np.asarray(thru, np.float64),
        q_occupancy=occupancy, dep_end_s=dep_end, t_s=t,
        line_rate_feasible=feasible,
        meta={"n_ports": n, "precision": precision, "use_pallas": use_pallas},
    )


def run_surrogate_batched(
    archs: Sequence[SwitchArch],
    bound: Union[BoundProtocol, Sequence[BoundProtocol]],
    trace,
    *,
    hw: Optional[Sequence[HardwareParams]] = None,
    back_annotation: bool = False,
    i_burst: float = 1.0,
    use_pallas: bool = False,
    interpret: bool = False,
    precision: str = "float64",
    quantiles: Sequence[float] = DEFAULT_QUANTILES,
    mesh=None,
) -> BatchedSurrogateResult:
    """Evaluate a whole candidate batch against one shared trace.

    The candidate axis of the device call is padded to
    ``repro.launch.mesh.bucket_size`` rows (replicas of row 0, stripped
    before anything is reduced), so every real row is bit-identical to an
    unpadded call.  ``mesh`` is an optional ``repro.launch.mesh.MeshSpec``
    (or anything its ``coerce`` accepts): when it names more than one shard
    the bucket is also a multiple of the shard count and the scan runs under
    ``shard_map`` across the device mesh — bit-identical to the
    single-device default (``mesh=None``).

    ``bound`` is one ``BoundProtocol`` shared by the batch, or — for the
    protocol/architecture co-design DSE — a per-candidate sequence (index-
    aligned with ``archs``): header wire-bytes then become a batch axis like
    bus width and η, and the batch still costs one jitted scan (the arrival
    timeline is the trace's, never rebuilt per candidate).

    Candidates may mix every architectural policy; only ``n_ports`` is a
    structural axis, so mixed-port batches are partitioned internally and the
    per-group results are stitched back in input order.

    ``use_pallas`` selects the Pallas crossbar kernel (float32), compiled
    for the TPU; ``interpret=True`` runs it in the Pallas interpreter
    instead (how the CPU tests validate it).

    Memory: the result holds per-candidate sample arrays ([B, m] latencies,
    occupancy and departure times — stage 3 consumes the samples), so host
    memory scales as O(B·m); at ~1e5-packet traces budget ~2.5 MB/candidate
    and chunk very large sweeps into multiple calls.
    """
    if use_pallas and precision == "float64":
        # the Pallas kernel is float32 by design (slack formulation); honour
        # that in the dtype, the meta, and the skipped enable_x64 — a silent
        # downcast would betray the documented bit-exactness of the f64 path
        precision = "float32"
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
    if not archs:
        return BatchedSurrogateResult(
            archs=[], hw=[], latency_ns=np.zeros((0, 0)),
            quantiles=np.zeros((0, len(quantiles))), quantile_qs=tuple(quantiles),
            throughput_gbps=np.zeros(0), q_occupancy=np.zeros((0, 0), np.int64),
            dep_end_s=np.zeros((0, 0)), t_s=np.zeros(0),
            line_rate_feasible=np.zeros(0, bool))
    if hw is None:
        source = "cycle_sim" if back_annotation else "model"
        hw = [annotate(a, b, source=source, i_burst=i_burst)
              for a, b in zip(archs, bounds)]
    hw = list(hw)
    if len(hw) != len(archs):
        raise ValueError(f"hw has {len(hw)} entries for {len(archs)} archs; "
                         "they must be index-aligned")

    groups: Dict[int, List[int]] = {}
    for i, a in enumerate(archs):
        groups.setdefault(a.n_ports, []).append(i)
    if len(groups) == 1:
        return _run_group(archs, bounds, trace, hw, use_pallas, interpret,
                          precision, quantiles, mesh_spec=mesh)

    parts = {n: _run_group([archs[i] for i in idx], [bounds[i] for i in idx],
                           trace, [hw[i] for i in idx], use_pallas, interpret,
                           precision, quantiles, mesh_spec=mesh)
             for n, idx in groups.items()}
    # stitch [B, m] arrays back in input order (m is shared: one trace)
    first = next(iter(parts.values()))
    merged = BatchedSurrogateResult(
        archs=archs, hw=hw,
        latency_ns=np.empty((len(archs),) + first.latency_ns.shape[1:]),
        quantiles=np.empty((len(archs), len(quantiles))),
        quantile_qs=tuple(quantiles),
        throughput_gbps=np.empty(len(archs)),
        q_occupancy=np.empty((len(archs),) + first.q_occupancy.shape[1:], np.int64),
        dep_end_s=np.empty((len(archs),) + first.dep_end_s.shape[1:]),
        t_s=first.t_s, line_rate_feasible=np.empty(len(archs), bool),
        meta={"precision": precision, "use_pallas": use_pallas})
    for n, idx in groups.items():
        part = parts[n]
        for row, i in enumerate(idx):
            merged.latency_ns[i] = part.latency_ns[row]
            merged.quantiles[i] = part.quantiles[row]
            merged.throughput_gbps[i] = part.throughput_gbps[row]
            merged.q_occupancy[i] = part.q_occupancy[row]
            merged.dep_end_s[i] = part.dep_end_s[row]
            merged.line_rate_feasible[i] = part.line_rate_feasible[row]
    return merged
