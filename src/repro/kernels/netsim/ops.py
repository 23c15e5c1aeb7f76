"""Dispatch + segmented-chain machinery for the netsim kernel family.

The stage-4 finite-VOQ recurrence looks inherently serial: every event's
admission depends on the departure ring of its (src, dst) VOQ, and every
departure depends on shared port state.  The kernels family splits those two
couplings and conquers each with the structure it actually has:

* **Port coupling** (departure times) keeps a scan, but a *lean* one — the
  admission-gated port replay (``ref.netsim_replay_abs_ref`` / the Pallas
  candidate-tiled form in ``kernel.py``), with no ``[B, N², D]`` ring.  The
  ring was ~80% of the old scan's measured wall-clock.  Round 1, where every
  event is admitted, needs no scan at all: its replay is the crossbar
  recurrence with earliest start ``now + pipe``, solved by the whole-trace
  fixed-point sweeps of ``repro.kernels.xbar.xbar_contend_sweep``.  Each
  event depends only on the last earlier event of its input and output
  port, so the system is triangular with one fixed point, the serial
  answer; the sweeps rise monotonically to it, and a sweep that changes
  nothing has reached it with every element the same ``max`` and ``+`` of
  the same operands as the scan — bit-identical.  A trace that does not
  settle within ``SWEEP_CAP`` sweeps runs the scan instead, in the same
  program.  The gated replays of later rounds keep their scan: their
  predecessors depend on each row's admission flags.
* **VOQ coupling** (admission flags) is *per-chain*: whether event k of
  chain (i, j) is dropped depends only on earlier events of the same chain.
  Inside a chain, admitted departures are FIFO (shared input and output
  port), so "the queue holds ``depth`` undeparted packets at ``now_k``" is
  exactly "the admission ``depth`` slots ago has not departed" — a
  segmented-scan question answered for **all events of all candidates at
  once** by ``segmented_admission`` (one segmented cumsum + one gather over
  the chain-sorted timeline, no replay).

The two halves meet in ``netsim_fixed_point``: speculate all-admitted, replay,
re-derive admissions, repeat.  Why the fixed point is the serial solution:
order events by arrival; event k's departure depends only on flags of events
< k, and event k's admission flag depends only on departures of its chain's
events < k.  By induction over k, any self-consistent (flags, departures)
pair equals the serial replay's — so when the loop closes, the result is
*exact*, not approximate (drop decisions bitwise, ``tests/test_netsim_kernels``).
In the common no-drop regime round 1 already closes; only rows that dropped
something iterate further, and a row that fails to close in ``max_rounds``
is reported unconverged so the caller can fall back to the serial oracle.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.retrace import track
from repro.analysis.spans import note, span
from repro.kernels.xbar import xbar_contend_sweep

from .kernel import netsim_replay_padded
from .ref import netsim_replay_abs_ref

__all__ = [
    "LANES", "ChainIndex", "build_chain_index", "segmented_admission",
    "segmented_occupancy", "lean_replay", "netsim_fixed_point",
    "kernel_available", "resolve_use_kernel",
]

LANES = 128          # TPU vector lane width: the tile's candidate block
SUBLANES = 8         # the tile's port axis pads to a multiple of this
EVENT_BLOCK = 1024   # events per tile grid step (the SMEM timeline block)


def kernel_available() -> bool:
    """Whether ``use_kernel="auto"`` resolves to the kernel path.

    The fixed-point path needs nothing beyond the JAX runtime the repo
    already requires (the Pallas tile is optional and off by default on
    CPU), so this is an environment kill-switch, not a capability probe:
    ``SPAC_NETSIM_KERNEL=off`` forces the bit-exact oracle engines
    everywhere without touching call sites."""
    return os.environ.get("SPAC_NETSIM_KERNEL", "").lower() not in {
        "0", "off", "false", "no"}


def resolve_use_kernel(value) -> bool:
    """Normalise the ``use_kernel`` knob: True/"on", False/"off", "auto"."""
    if isinstance(value, bool):
        return value
    if value is None:
        return kernel_available()
    v = str(value).lower()
    if v in {"on", "true", "1", "yes"}:
        return True
    if v in {"off", "false", "0", "no"}:
        return False
    if v == "auto":
        return kernel_available()
    raise ValueError(f"use_kernel must be 'auto', 'on'/'off' or a bool, "
                     f"got {value!r}")


# --------------------------------------------------------------------------
# chain index: the segmented view of the shared timeline
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ChainIndex:
    """Per-(src,dst) chain structure of one time-ordered event timeline.

    ``perm`` stably sorts events by chain id (time order preserved inside a
    chain), ``inv`` undoes it, ``seg_start[p]``/``rank[p]`` give, for the
    event at *permuted* position p, its chain's first permuted position and
    its arrival rank within the chain.  Pure function of (timeline, n_ports)
    — computed once per trace by ``sim.timeline`` and reused across every
    generation, candidate and campaign scenario."""

    perm: np.ndarray        # [m] intp — stable argsort of chain ids
    inv: np.ndarray         # [m] intp — inverse permutation
    seg_start: np.ndarray   # [m] int32 — chain block start, permuted domain
    rank: np.ndarray        # [m] int32 — arrivals-before-me within my chain
    n_chains: int


def build_chain_index(qid: np.ndarray) -> ChainIndex:
    m = qid.size
    perm = np.argsort(qid, kind="stable")
    g = qid[perm]
    first = np.ones(m, bool)
    first[1:] = g[1:] != g[:-1]
    starts = np.nonzero(first)[0]
    run_ids = np.cumsum(first) - 1
    seg_start = starts[run_ids].astype(np.int32) if m else np.zeros(0, np.int32)
    rank = (np.arange(m, dtype=np.int32) - seg_start).astype(np.int32)
    inv = np.empty(m, np.intp)
    inv[perm] = np.arange(m)
    return ChainIndex(perm=perm.astype(np.intp), inv=inv, seg_start=seg_start,
                      rank=rank, n_chains=int(starts.size))


# --------------------------------------------------------------------------
# segmented admission: finite-VOQ fullness without replay
# --------------------------------------------------------------------------

def segmented_admission(end: np.ndarray, admit: np.ndarray, now: np.ndarray,
                        depth: np.ndarray, chain: ChainIndex) -> np.ndarray:
    """Derive next-round admission flags from a candidate replay.

    Given departure times ``end`` produced under speculative flags ``admit``,
    answer for every event of every candidate: *with these departures, would
    my VOQ have been full when I arrived?*  FIFO-per-chain makes that "has
    the admission ``depth`` slots before me departed by ``now``" — a
    segmented cumulative count (``na`` = admissions before me in my chain)
    plus one gather into a compacted per-chain admission array.  All numpy,
    no scan: one pass covers the whole [B, m] block.
    """
    b_n, m = end.shape
    perm, seg_start = chain.perm, chain.seg_start
    a_s = admit[:, perm]
    e_s = end[:, perm]
    n_s = now[perm]
    cum = np.cumsum(a_s, axis=1, dtype=np.int32)
    excl = cum - a_s                                    # admits before me, global
    na = excl - np.take(excl, seg_start, axis=1)        # ... within my chain
    # compact admitted departure times to their admission-rank slots; dropped
    # events park in the spare column m (never read: full needs na >= depth,
    # and that rank's slot was written by a real admission)
    slot = np.where(a_s, seg_start + na, m)
    comp = np.zeros((b_n, m + 1))
    rows = np.arange(b_n, dtype=np.intp)[:, None] * (m + 1)
    comp.ravel()[(slot + rows).ravel()] = e_s.ravel()
    r = na - depth[:, None].astype(np.int32)
    look = np.where(r >= 0, seg_start + r, m)
    oldest = np.take(comp.ravel(), (look + rows).ravel()).reshape(b_n, m)
    full = (r >= 0) & (oldest > n_s[None, :])
    return (~full)[:, chain.inv]


# --------------------------------------------------------------------------
# segmented occupancy: stage 2's per-VOQ counts without the per-row loop
# --------------------------------------------------------------------------

def segmented_occupancy(t: np.ndarray, dep: np.ndarray,
                        chain: ChainIndex) -> np.ndarray:
    """Exact per-VOQ occupancy at arrival instants, one searchsorted total.

    The serial reference (``batched_surrogate._exact_occupancy``) runs one
    ``searchsorted`` per candidate row.  Occupancy at event k is
    ``(chain arrivals ≤ k) − (chain departures ≤ now_k) − 1`` — a prefix
    count over chain segments.  Departures are FIFO inside a chain (shared
    ports), so the chain-major key ``chain·span + time`` is globally sorted
    per row; adding a per-row offset makes the whole [B, m] block one sorted
    key stream and a *single* flat ``np.searchsorted`` answers every
    (candidate, event) query at once.

    Precision: the composite key spends ~log2(B·n²) mantissa bits on the
    (row, chain) id — ≈14 bits at B=256, n=8, leaving time resolution of
    span·2⁻³⁸ ≈ femtoseconds on the registry traces, far below any service
    time.  Counts are integers and bit-identical to the serial reference on
    every registry workload (asserted in ``tests/test_netsim_kernels.py``).
    """
    b_n, m = dep.shape
    perm = chain.perm
    pos = np.arange(m, dtype=np.int64)
    span = max(float(dep.max(initial=0.0)), float(t.max(initial=0.0))) + 1.0
    # chain id per permuted slot: seg_start is constant within a chain and
    # unique across chains, so it serves as a compact chain id
    g = chain.seg_start.astype(np.float64)
    key_dep = g[None, :] * span + dep[:, perm]
    key_arr = g * span + t[perm]
    big = (float(chain.seg_start[-1]) + 2.0) * span if m else 1.0
    rows = np.arange(b_n, dtype=np.float64)[:, None] * big
    fd = (key_dep + rows).ravel()
    fa = (key_arr[None, :] + rows).ravel()
    departed = (np.searchsorted(fd, fa, side="right").reshape(b_n, m)
                - np.arange(b_n, dtype=np.int64)[:, None] * m)
    occ_s = (pos[None, :] + 1) - departed - 1
    return occ_s[:, chain.inv]


# --------------------------------------------------------------------------
# the lean replay: tracked jit, sharded builder, Pallas tile
# --------------------------------------------------------------------------

def _round1_body(now, src, dst, svc_t, pipe, depth, perm, seg_start, rank,
                 *, n_ports):
    """Fused first round: ungated replay + all-admitted fullness check.

    With all-ones flags the gated recurrence degenerates to the plain port
    replay — the crossbar recurrence with earliest start ``now + pipe``, so
    it runs as the fixed-point sweeps of ``xbar_contend_sweep`` (bitwise the
    serial scan; see the module docstring) — and the admission question
    needs no compaction at all — the ``rank − depth``-th event of my chain
    *is* the depth-ago admission, so one ``take_along_axis`` answers
    fullness for the whole batch.  Returns the replay, a per-row "round 1
    is the fixed point" flag, and the sweep counters ``(sweeps,
    fell_back)``; rows where the flag holds (every row, in the sized
    no-drop regime) are done after this one call."""
    end_t, sweeps, fell_back = xbar_contend_sweep(
        now[:, None] + pipe[None, :], src, dst, svc_t, n_ports=n_ports)
    end = end_t.T                                           # [B, m]
    e_s = jnp.take(end, perm, axis=1)
    n_s = jnp.take(now, perm)
    r = rank[None, :] - depth[:, None]                      # [B, m] int32
    look = jnp.clip(seg_start[None, :] + r, 0, max(e_s.shape[1] - 1, 0))
    oldest = jnp.take_along_axis(e_s, look, axis=1)
    full = (r >= 0) & (oldest > n_s[None, :])
    ok = ~jnp.any(full, axis=1)
    return end, ok, sweeps, fell_back


_round1 = track("netsim.kernel.round1",
                jax.jit(_round1_body, static_argnames=("n_ports",)))

_gated_replay = track("netsim.kernel.replay", netsim_replay_abs_ref)


@functools.lru_cache(maxsize=None)
def _sharded_round1(mesh, n_ports):
    """Round 1 under ``shard_map``: candidate axis split over every mesh
    axis, timeline and chain structure replicated.  Rowwise — no collectives
    — so each shard is bitwise the single-device call on its slice; each
    returns its own sweep counters, one per shard."""
    from jax.sharding import PartitionSpec as P

    names = tuple(mesh.axis_names)
    cand = P(names)
    rep = P()

    def body(*args):
        end, ok, sweeps, fell_back = _round1_body(*args, n_ports=n_ports)
        return end, ok, sweeps[None], fell_back[None]

    name = (f"netsim.kernel.round1.sharded["
            f"{'x'.join(map(str, mesh.devices.shape))} "
            f"{','.join(names)} n_ports={n_ports}]")
    return track(name, jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(rep, rep, rep, P(None, names), cand, cand, rep, rep, rep),
        out_specs=(cand,) * 4, check_vma=False)))


@functools.lru_cache(maxsize=None)
def _sharded_gated_replay(mesh, n_ports):
    from jax.sharding import PartitionSpec as P

    names = tuple(mesh.axis_names)
    cand = P(names)
    rep = P()

    def body(now, src, dst, svc, pipe, admit):
        return netsim_replay_abs_ref(now, src, dst, svc, pipe, admit,
                                     n_ports=n_ports)

    name = (f"netsim.kernel.replay.sharded["
            f"{'x'.join(map(str, mesh.devices.shape))} "
            f"{','.join(names)} n_ports={n_ports}]")
    return track(name, jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(rep, rep, rep, cand, cand, cand),
        out_specs=cand, check_vma=False)))


def lean_replay(now, src, dst, svc, pipe, admit, *, n_ports: int,
                use_pallas: bool = False, interpret: bool = False,
                block_b: int = LANES):
    """The admission-gated lean replay, oracle or Pallas tile.

    Oracle path (default): the jitted float64 ``lax.scan``
    (``ref.netsim_replay_abs_ref``), absolute departure times, bit-exact
    against the serial model.  Pallas path: the float32 slack-formulation
    kernel with the candidate and event axes tiled onto the grid; returns
    departure *offsets* (``end − now``), parity at float32 tolerance.
    ``interpret=True`` runs the tile in the Pallas interpreter (how the CPU
    tests validate it); the default compiles it for the TPU."""
    if not use_pallas:
        return netsim_replay_abs_ref(
            jnp.asarray(now), jnp.asarray(src, jnp.int32),
            jnp.asarray(dst, jnp.int32), jnp.asarray(svc),
            jnp.asarray(pipe), jnp.asarray(admit), n_ports=n_ports)
    now = np.asarray(now, np.float64)
    b_n, m = np.asarray(svc).shape
    n_pad = -(-n_ports // SUBLANES) * SUBLANES
    b_pad = -(-b_n // block_b) * block_b
    m_pad = -(-m // EVENT_BLOCK) * EVENT_BLOCK
    # event-major [m, B] blocks; pad events sit after every real one with
    # admit = 0, so they never touch the port state real events read
    dnow = np.zeros(m_pad, np.float32)
    dnow[:m] = np.diff(now, prepend=0.0)
    src_p = np.zeros(m_pad, np.int32)
    src_p[:m] = src
    dst_p = np.zeros(m_pad, np.int32)
    dst_p[:m] = dst
    svc_p = np.zeros((m_pad, b_pad), np.float32)
    svc_p[:m, :b_n] = np.asarray(svc, np.float32).T
    ad_p = np.zeros((m_pad, b_pad), np.float32)
    ad_p[:m, :b_n] = np.asarray(admit, np.float32).T
    pipe_p = np.zeros((1, b_pad), np.float32)
    pipe_p[0, :b_n] = np.asarray(pipe, np.float32)
    dep = netsim_replay_padded(
        jnp.asarray(dnow), jnp.asarray(src_p), jnp.asarray(dst_p),
        jnp.asarray(svc_p), jnp.asarray(ad_p), jnp.asarray(pipe_p),
        n_pad=n_pad, block_b=block_b, block_m=EVENT_BLOCK,
        interpret=interpret)
    return dep[:m, :b_n].T


def _pad_rows(a: np.ndarray, size: int) -> np.ndarray:
    """Pad the candidate axis to ``size`` by replicating row 0 (a no-op
    workload: rowwise engines ignore replicas, callers strip them)."""
    if a.shape[0] == size:
        return a
    reps = np.repeat(a[:1], size - a.shape[0], axis=0)
    return np.concatenate([a, reps], axis=0)


def _bucket(b_n: int, k: int) -> int:
    """Compile-friendly batch size: next power of two, then up to a multiple
    of the shard count — subset iterations reuse O(log B) compiled shapes."""
    size = 1 << max(b_n - 1, 0).bit_length()
    if k > 1:
        size = -(-size // k) * k
    return size


def netsim_fixed_point(
    now: np.ndarray,       # [m] sorted switch-arrival times
    src: np.ndarray,       # [m] int32
    dst: np.ndarray,       # [m] int32
    svc: np.ndarray,       # [B, m] float64
    pipe: np.ndarray,      # [B] float64
    depth: np.ndarray,     # [B] int — per-candidate VOQ depth (>= 1)
    *,
    n_ports: int,
    chain: ChainIndex,
    mesh_spec=None,
    max_rounds: int = 24,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """Speculative fixed point: (end [B,m], admit [B,m], converged [B], rounds).

    Round 1 runs the fused ungated replay + fullness check for the whole
    batch (one jitted call, mesh-sharded when ``mesh_spec`` names devices).
    Rows whose all-admitted replay is already self-consistent — every row,
    when stage-3 sizing did its job — are final.  The rest iterate
    replay ↔ ``segmented_admission`` on the row subset only (padded to a
    power-of-two bucket so compiles stay O(log B)); unconverged rows after
    ``max_rounds`` are flagged for the caller's serial fallback.  Callers
    must handle ``depth < 1`` rows themselves (serial semantics drop every
    packet; no replay needed)."""
    b_n, m = svc.shape
    if np.any(depth < 1):
        raise ValueError("netsim_fixed_point requires depth >= 1 rows")
    k = 1 if mesh_spec is None else mesh_spec.shard_axis
    depth32 = np.minimum(depth, np.int64(2**31 - 1)).astype(np.int32)

    round1 = _sharded_round1(mesh_spec.build(), n_ports) if k > 1 else _round1
    # the device call: copies in (the timeline stays for later rounds), the
    # fused round, and the fetch back
    with span("spac.stage4.round1", jit=round1):
        now_j = jnp.asarray(now)
        src_j = jnp.asarray(src, jnp.int32)
        dst_j = jnp.asarray(dst, jnp.int32)
        perm_j = jnp.asarray(chain.perm, jnp.int32)
        seg_j = jnp.asarray(chain.seg_start, jnp.int32)
        rank_j = jnp.asarray(chain.rank, jnp.int32)
        if k > 1:
            from repro.launch.mesh import shard_pad
            out = round1(now_j, src_j, dst_j,
                         jnp.asarray(shard_pad(svc, k).T),
                         jnp.asarray(shard_pad(pipe, k)),
                         jnp.asarray(shard_pad(depth32, k)),
                         perm_j, seg_j, rank_j)
        else:
            out = round1(now_j, src_j, dst_j, jnp.asarray(svc.T),
                         jnp.asarray(pipe), jnp.asarray(depth32),
                         perm_j, seg_j, rank_j, n_ports=n_ports)
        end, ok, sweeps, fell_back = jax.device_get(out)
        # the slowest shard's sweeps; whether any shard fell back
        note(sweeps=int(np.max(sweeps)), scan_fallback=int(np.max(fell_back)))
        # np.array (not asarray): fetched arrays may be read-only and the
        # subset iteration scatters into end below
        end = np.array(end[:b_n])
        ok = ok[:b_n]
    admit = np.ones((b_n, m), bool)
    converged = ok.copy()
    if bool(ok.all()):
        return end, admit, converged, 1

    rows = np.nonzero(~ok)[0]
    sub_svc, sub_pipe = svc[rows], pipe[rows]
    sub_depth = depth32[rows]
    sub_end = end[rows]
    with span("spac.stage4.admission", round=1):
        cur = segmented_admission(sub_end, np.ones((rows.size, m), bool), now,
                                  sub_depth, chain)
    replay = (_sharded_gated_replay(mesh_spec.build(), n_ports) if k > 1
              else _gated_replay)
    rounds = 1
    conv_sub = np.zeros(rows.size, bool)
    while rounds < max_rounds:
        rounds += 1
        size = _bucket(rows.size, k)
        svc_p = _pad_rows(sub_svc, size)
        admit_p = _pad_rows(cur, size)
        pipe_p = _pad_rows(sub_pipe, size)
        with span("spac.stage4.replay", jit=replay, round=rounds):
            if k > 1:
                sub_end = np.asarray(replay(
                    now_j, src_j, dst_j, jnp.asarray(svc_p),
                    jnp.asarray(pipe_p), jnp.asarray(admit_p)))[:rows.size]
            else:
                sub_end = np.asarray(replay(
                    now_j, src_j, dst_j, jnp.asarray(svc_p),
                    jnp.asarray(pipe_p), jnp.asarray(admit_p),
                    n_ports=n_ports))[:rows.size]
        with span("spac.stage4.admission", round=rounds):
            derived = segmented_admission(sub_end, cur, now, sub_depth, chain)
        eq = (derived == cur).all(axis=1)
        conv_sub = np.asarray(eq)
        if bool(eq.all()):
            break
        cur = derived
    end[rows] = sub_end
    admit[rows] = cur
    converged[rows] = conv_sub
    return end, admit, converged, rounds
