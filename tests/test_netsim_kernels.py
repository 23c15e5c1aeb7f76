"""Segmented netsim kernels vs the oracle engines (PR: kernels/netsim).

Contract under test: with ``use_kernel`` enabled the batched engines produce
**bit-identical** results to the oracle paths — drop counts exact, latency
arrays ``assert_array_equal`` (the documented f64 tolerance is 0), occupancy
counts integer-equal — across workloads, VOQ kinds and sized depths; the
Pallas tile matches the float32 slack oracle bitwise in interpret mode; the
trace-keyed timeline memo sorts each trace exactly once across a whole
NSGA-II run; and the ``use_kernel`` knob round-trips through ``Fidelity``
JSON and composes with the device mesh bit-identically.
"""

import json
import math

import numpy as np
import pytest

from repro.core import (ArchRequest, ForwardTableKind, SLA, SchedulerKind,
                        SwitchArch, VOQKind, bind, compressed_protocol,
                        enumerate_candidates)
from repro.kernels import netsim as kn
from repro.sim import (run_netsim, run_netsim_batched, run_surrogate,
                       run_surrogate_batched)
from repro.sim import timeline as tlmod
from repro.sim.switch_problem import SwitchDSEProblem
from repro.traces import datacenter, hft
from repro.traces.base import Trace

BOUND = bind(compressed_protocol(addr_bits=4, length_bits=6), flit_bits=256)


def _traces():
    return {
        "hft": hft(seed=0),
        "datacenter": datacenter(seed=0, n_ports=8, duration_s=400e-6, load=0.8),
    }


def _sized_candidates():
    base = enumerate_candidates(ArchRequest(n_ports=8, addr_bits=4))
    assert {a.voq for a in base} == {VOQKind.NXN, VOQKind.SHARED}
    return [a.with_depth(d) for a in base[:12] for d in (2, 8, 64)]


def _assert_results_identical(kernel_results, oracle_results):
    for b, s in zip(kernel_results, oracle_results):
        assert b.drop_rate == s.drop_rate
        assert b.p99_latency_ns == s.p99_latency_ns
        assert b.mean_latency_ns == s.mean_latency_ns
        assert b.throughput_gbps == s.throughput_gbps
        assert b.meta["delivered"] == s.meta["delivered"]
        np.testing.assert_array_equal(b.meta["latency_ns"],
                                      s.meta["latency_ns"])


# --------------------------------------------------------------------------
# stage-4 parity matrix: workloads x VOQ kinds x sized depths
# --------------------------------------------------------------------------

@pytest.mark.parametrize("workload", ["hft", "datacenter"])
def test_stage4_kernel_parity_matrix(workload):
    """Kernel vs oracle across both VOQ kinds at depths 2/8/64 — drops
    bit-exact (the small depths genuinely bind), latency bit-identical."""
    tr = _traces()[workload]
    cands = _sized_candidates()
    vk = run_netsim_batched(cands, BOUND, tr, back_annotation=False,
                            use_kernel=True)
    vo = run_netsim_batched(cands, BOUND, tr, back_annotation=False,
                            use_kernel=False)
    assert any(v.drop_rate > 0 for v in vo)      # the depths actually bind
    _assert_results_identical(vk, vo)


def test_stage4_kernel_matches_serial_oracle():
    """Straight to the heapq oracle (not just the ring scan) on a dropping
    workload — the fixed point's drop decisions are the serial decisions."""
    tr = hft(seed=0)
    cands = [a.with_depth(2) for a in
             enumerate_candidates(ArchRequest(n_ports=8, addr_bits=4))[:6]]
    vk = run_netsim_batched(cands, BOUND, tr, back_annotation=False,
                            use_kernel=True)
    vs = [run_netsim(a, BOUND, tr, back_annotation=False) for a in cands]
    assert any(v.drop_rate > 0 for v in vs)
    _assert_results_identical(vk, vs)


def test_shared_cap_fallback_on_kernel_path():
    """Candidates whose shared N·depth cap binds must take the flagged
    serial fallback on the kernel path too, and still match the oracle."""
    n = 8
    rng = np.random.default_rng(0)
    per_src = 120
    times = np.concatenate([np.arange(per_src) * 2.2e-7 + s * 1e-9
                            for s in range(n)])
    srcs = np.concatenate([np.full(per_src, s) for s in range(n)])
    dsts = np.concatenate([rng.integers(0, 4, per_src) for _ in range(n)])
    tr = Trace("incast4", times, srcs, dsts, np.full(n * per_src, 200), n,
               link_gbps=10.0)
    cands = [SwitchArch(n_ports=8, bus_bits=bw,
                        fwd=ForwardTableKind.FULL_LOOKUP, voq=voq,
                        sched=SchedulerKind.RR, voq_depth=d, addr_bits=4)
             for bw in (128, 512)
             for voq in (VOQKind.SHARED, VOQKind.NXN) for d in (8, 16)]
    vk = run_netsim_batched(cands, BOUND, tr, back_annotation=False,
                            use_kernel=True)
    vo = run_netsim_batched(cands, BOUND, tr, back_annotation=False)
    assert any(v.meta.get("shared_cap_fallback") for v in vk)
    for a, b, s in zip(cands, vk, vo):
        assert (b.meta.get("fallback") == "shared_cap") == \
               (s.meta.get("fallback") == "shared_cap"), a.short()
    _assert_results_identical(vk, vo)


# --------------------------------------------------------------------------
# edges: degenerate depth, empty trace, single candidate, single chain
# --------------------------------------------------------------------------

def test_degenerate_depth_kernel():
    tr = hft(seed=0).head(64)
    cands = [_sized_candidates()[0].with_depth(0),
             _sized_candidates()[1].with_depth(8)]
    vk = run_netsim_batched(cands, BOUND, tr, back_annotation=False,
                            use_kernel=True)
    assert vk[0].meta["fallback"] == "degenerate_depth"
    assert vk[0].drop_rate == 1.0
    assert "fallback" not in vk[1].meta
    vo = run_netsim_batched(cands, BOUND, tr, back_annotation=False)
    _assert_results_identical(vk, vo)


def test_empty_trace_kernel():
    empty = Trace("empty", np.zeros(0), np.zeros(0, np.int32),
                  np.zeros(0, np.int32), np.zeros(0, np.int64), 8)
    vk = run_netsim_batched(_sized_candidates()[:3], BOUND, empty,
                            back_annotation=False, use_kernel=True)
    assert len(vk) == 3
    for v in vk:
        assert v.drop_rate == 0.0 and math.isinf(v.p99_latency_ns)
    sk = run_surrogate_batched(_sized_candidates()[:3], BOUND, empty,
                               back_annotation=False)
    assert sk.q_occupancy.shape == (3, 0)


def test_single_candidate_kernel():
    tr = hft(seed=1)
    a = _sized_candidates()[0]
    [vk] = run_netsim_batched([a], BOUND, tr, back_annotation=False,
                              use_kernel=True)
    vs = run_netsim(a, BOUND, tr, back_annotation=False)
    assert vk.drop_rate == vs.drop_rate
    np.testing.assert_array_equal(vk.meta["latency_ns"], vs.meta["latency_ns"])


def test_single_chain_trace():
    """All events on one (src, dst) pair: the segmented pass degenerates to
    one chain and must still reproduce the serial model exactly."""
    m = 96
    tr = Trace("onechain", np.arange(m) * 3e-7,
               np.zeros(m, np.int32), np.ones(m, np.int32),
               np.full(m, 300, np.int64), 8, link_gbps=10.0)
    cands = [a.with_depth(d) for a in
             enumerate_candidates(ArchRequest(n_ports=8, addr_bits=4))[:4]
             for d in (2, 8)]
    vk = run_netsim_batched(cands, BOUND, tr, back_annotation=False,
                            use_kernel=True)
    vs = [run_netsim(a, BOUND, tr, back_annotation=False) for a in cands]
    _assert_results_identical(vk, vs)


def test_duplicate_rows_fan_out_with_fresh_meta():
    """NSGA-II batches repeat genomes; deduped rows must come back as
    distinct results whose meta dicts are independently mutable."""
    tr = hft(seed=0).head(256)
    a = _sized_candidates()[0]
    vk = run_netsim_batched([a, a, a], BOUND, tr, back_annotation=False,
                            use_kernel=True)
    assert vk[0].p99_latency_ns == vk[1].p99_latency_ns == vk[2].p99_latency_ns
    vk[0].meta["marker"] = "x"
    assert "marker" not in vk[1].meta


# --------------------------------------------------------------------------
# stage-2: segmented occupancy + lean replay oracles
# --------------------------------------------------------------------------

def _serial_occupancy(t, qid, dep):
    """The serial engine's per-queue count: one ``searchsorted`` per queue
    and row over that queue's own departures."""
    occ = np.empty(dep.shape, np.int64)
    for q in np.unique(qid):
        sel = np.nonzero(qid == q)[0]
        for b in range(dep.shape[0]):
            occ[b, sel] = np.arange(sel.size) - np.searchsorted(
                dep[b, sel], t[sel], side="right")
    return occ


@pytest.mark.parametrize("workload", ["hft", "datacenter", "one_chain"])
def test_stage2_kernel_occupancy_bitwise(workload):
    """The one occupancy count equals the serial engine's, packet by packet,
    through the batched engine; shared-buffer occupancy too."""
    if workload == "one_chain":
        m = 96
        tr = Trace("onechain", np.arange(m) * 3e-8, np.zeros(m, np.int32),
                   np.ones(m, np.int32), np.full(m, 300, np.int64), 8,
                   link_gbps=10.0)
    else:
        tr = _traces()[workload]
    cands = enumerate_candidates(ArchRequest(n_ports=8, addr_bits=4))[:10]
    sk = run_surrogate_batched(cands, BOUND, tr, back_annotation=False)
    tl = tlmod.stage2_timeline(tr, 8)
    np.testing.assert_array_equal(
        sk.q_occupancy, _serial_occupancy(tl.t, tl.qid, sk.dep_end_s))
    for a, rk in zip(cands, sk.results()):
        ro = run_surrogate(a, BOUND, tr, back_annotation=False)
        np.testing.assert_array_equal(rk.q_occupancy, ro.q_occupancy)
        x, y = rk.meta["shared_occupancy"], ro.meta["shared_occupancy"]
        assert (x is None) == (y is None)
        if x is not None:
            np.testing.assert_array_equal(x, y)


#: gaps after an arrival at which a departure of the same queue lands
NEAR_TIES = (0.0, 1e-15, 1e-12, 1e-10)


@pytest.mark.parametrize("gap", NEAR_TIES)
def test_occupancy_exact_at_near_ties(gap):
    """A departure 0, 1e-15, 1e-12 or 1e-10 s after an arrival of its own
    queue, in the chain that ends the permuted timeline (positions near
    m = 100,000) and in each of B = 40 rows, over a 0.16 s trace: a key that
    adds the time to a row or chain offset in float64 has a spacing of
    about B x m x span x 2^-52 = 1.4e-10 s there and miscounts these.  The
    count equals the per-queue serial count, packet for packet."""
    rng = np.random.default_rng(7)
    m, b_n, n_q = 100_000, 40, 64
    t = np.cumsum(rng.uniform(0.5e-6, 2.7e-6, m))
    t -= t[0]
    qid = rng.integers(0, n_q, m)
    dep = np.empty((b_n, m))
    for q in range(n_q):
        sel = np.nonzero(qid == q)[0]
        # FIFO within a queue, each departure after its own arrival
        dep[:, sel] = np.maximum.accumulate(
            t[sel] + rng.uniform(1e-7, 5e-6, (b_n, sel.size)), axis=1)
    # row b: the last queue's member before its (b+1)-th last arrival
    # departs ``gap`` after that arrival; later members depart later still
    members = np.nonzero(qid == n_q - 1)[0]
    for b in range(b_n):
        k = members[-1 - b]
        j = members[-2 - b]
        assert dep[b, members[:-2 - b]].max() < t[k]
        dep[b, j] = t[k] + gap
    chain = kn.build_chain_index(qid)
    assert chain.seg_start[chain.inv[members[-1]]] > 0.98 * m
    got = kn.segmented_occupancy(t, dep, chain)
    want = _serial_occupancy(t, qid, dep)
    np.testing.assert_array_equal(got, want)
    # at an exact tie the departure has happened (the serial side="right")
    for b in range(b_n):
        k = members[-1 - b]
        assert want[b, k] == (0 if gap == 0.0 else 1)


def _all_stages(cands, tr):
    """Stage 2, and stage 4 on both engines, over ``cands`` sized at
    depths 2 (drops, so later rounds replay) and 64."""
    sized = [a.with_depth((2, 64)[i % 2]) for i, a in enumerate(cands)]
    return (run_surrogate_batched(cands, BOUND, tr, back_annotation=False),
            run_netsim_batched(sized, BOUND, tr, back_annotation=False,
                               use_kernel=True),
            run_netsim_batched(sized, BOUND, tr, back_annotation=False,
                               use_kernel=False))


@pytest.mark.parametrize("width", [1, 9, 33])
def test_pad_rows_leave_real_rows_bit_identical(width, monkeypatch):
    """Every device call pads its candidate axis to a bucket width (1 -> 8,
    9 -> 16, 33 -> 48); each real row's outputs are bit for bit those of
    the same calls made at the exact width."""
    import repro.launch.mesh as mesh_mod

    assert mesh_mod.bucket_size(width) > width
    tr = hft(seed=0).head(512)
    base = enumerate_candidates(ArchRequest(n_ports=8, addr_bits=4))
    cands = [base[i % len(base)] for i in range(width)]
    padded = _all_stages(cands, tr)
    monkeypatch.setattr(mesh_mod, "bucket_pad", lambda a, k=1, axis=0: a)
    exact = _all_stages(cands, tr)
    (s_p, vk_p, vo_p), (s_e, vk_e, vo_e) = padded, exact
    for f in ("dep_end_s", "latency_ns", "quantiles", "throughput_gbps"):
        got, want = getattr(s_p, f), getattr(s_e, f)
        np.testing.assert_array_equal(got.view(np.int64), want.view(np.int64))
    np.testing.assert_array_equal(s_p.q_occupancy, s_e.q_occupancy)
    if width > 1:
        assert any(v.drop_rate > 0 for v in vk_e)
    _assert_results_identical(vk_p, vk_e)
    _assert_results_identical(vo_p, vo_e)


@pytest.mark.parametrize("n_ports,b_n,m,seed,horizon", [
    (8, 5, 160, 3, 1e-4),
    # two candidate blocks and three event blocks (the last one padded):
    # port state crosses event-block boundaries in the tile's VMEM scratch
    (32, 130, 2500, 8, 2e-4)], ids=["one_block", "across_blocks"])
def test_pallas_tile_matches_slack_oracle_bitwise(n_ports, b_n, m, seed,
                                                  horizon):
    """The candidate-tiled Pallas kernel is bit-for-bit the float32 slack
    reference in interpret mode (same formulation, same dtype)."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    now = np.sort(rng.uniform(0, horizon, m))
    src = rng.integers(0, n_ports, m).astype(np.int32)
    dst = rng.integers(0, n_ports, m).astype(np.int32)
    svc = rng.uniform(1e-8, 4e-7, (b_n, m)).astype(np.float32)
    pipe = rng.uniform(0, 5e-8, b_n).astype(np.float32)
    admit = rng.random((b_n, m)) > 0.2
    dnow = np.diff(now, prepend=0.0).astype(np.float32)
    ref = np.asarray(kn.netsim_replay_slack_ref(
        jnp.asarray(dnow), jnp.asarray(src), jnp.asarray(dst),
        jnp.asarray(svc), jnp.asarray(pipe), jnp.asarray(admit),
        n_ports=n_ports))
    tile = np.asarray(kn.lean_replay(now, src, dst, svc, pipe, admit,
                                     n_ports=n_ports, use_pallas=True,
                                     interpret=True))
    np.testing.assert_array_equal(tile, ref)


def test_abs_oracle_is_gated_replay():
    """The f64 absolute oracle under all-ones flags equals the slack form
    reconstructed to absolute times within f32-off tolerance, and its gated
    updates actually gate: a dropped event must leave port state alone."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(5)
    n_ports, m = 4, 64
    now = np.sort(rng.uniform(0, 1e-5, m))
    src = rng.integers(0, n_ports, m).astype(np.int32)
    dst = rng.integers(0, n_ports, m).astype(np.int32)
    svc = rng.uniform(1e-8, 2e-7, (1, m))
    pipe = np.array([2e-8])
    all_on = np.ones((1, m), bool)
    gated = all_on.copy()
    gated[0, 10] = False
    with jax.enable_x64():
        e_on = np.asarray(kn.netsim_replay_abs_ref(
            jnp.asarray(now), jnp.asarray(src), jnp.asarray(dst),
            jnp.asarray(svc), jnp.asarray(pipe), jnp.asarray(all_on),
            n_ports=n_ports))
        e_gate = np.asarray(kn.netsim_replay_abs_ref(
            jnp.asarray(now), jnp.asarray(src), jnp.asarray(dst),
            jnp.asarray(svc), jnp.asarray(pipe), jnp.asarray(gated),
            n_ports=n_ports))
    # the dropped event still gets an end time, but successors on its ports
    # must not wait for it
    later_same_port = [k for k in range(11, m)
                       if src[k] == src[10] or dst[k] == dst[10]]
    assert later_same_port
    assert np.all(e_gate[0, later_same_port] <= e_on[0, later_same_port])


def test_segmented_admission_matches_bruteforce():
    """The compacted segmented pass equals the obvious per-chain loop."""
    rng = np.random.default_rng(11)
    b_n, m, n_chains, depth = 7, 200, 9, 3
    qid = rng.integers(0, n_chains, m)
    chain = kn.build_chain_index(qid)
    now = np.sort(rng.uniform(0, 1.0, m))
    end = now[None, :] + rng.uniform(0.0, 0.4, (b_n, m))
    admit = rng.random((b_n, m)) > 0.3
    depths = rng.integers(1, depth + 2, b_n)
    got = kn.segmented_admission(end, admit, now, depths, chain)
    want = np.empty_like(got)
    for b in range(b_n):
        for k in range(m):
            mine = [j for j in range(k) if qid[j] == qid[k] and admit[b, j]]
            na = len(mine)
            full = (na >= depths[b]
                    and end[b, mine[na - depths[b]]] > now[k])
            want[b, k] = not full
    np.testing.assert_array_equal(got, want)


# --------------------------------------------------------------------------
# timeline memo: each trace sorted exactly once across a whole search
# --------------------------------------------------------------------------

def test_timeline_sorted_once_across_nsga2_run():
    from repro.api.scenario import SearchSpec
    from repro.core.search import run_search

    tlmod.clear()
    tr = hft(seed=0).head(1024)
    problem = SwitchDSEProblem(
        ArchRequest(n_ports=8, addr_bits=4), BOUND, tr,
        back_annotation=False, use_kernel="on")
    outcome = run_search(problem, SearchSpec(population=12, generations=10,
                                             seed=3),
                         SLA(drop_rate=1e-3), delta=0.2)
    # stage 4 runs post-search in the runner; emulate its repeated
    # verify_batch calls over the survivors (plus a re-verification pass,
    # as campaign scenarios do)
    front = [a for a, _ in outcome.valid][:6]
    assert front
    problem.verify_batch(front)
    problem.verify_batch(front)
    s = tlmod.stats()
    assert s["stage2_builds"] >= 1 and s["stage4_builds"] >= 1
    # the satellite contract: every (trace, structure) timeline was built —
    # i.e. argsort/lexsort/serialisation ran — exactly once for the whole run
    assert all(v == 1 for v in s["builds_by_key"].values()), s["builds_by_key"]
    assert s["stage2_hits"] + s["stage4_hits"] > 0


def test_timeline_memo_hits_across_trace_rebuilds():
    tlmod.clear()
    a, b = hft(seed=0).head(128), hft(seed=0).head(128)
    t1 = tlmod.stage2_timeline(a, 8)
    t2 = tlmod.stage2_timeline(b, 8)        # same content, fresh instance
    assert t1 is t2
    assert tlmod.stats()["stage2_builds"] == 1
    assert tlmod.stats()["stage2_hits"] == 1


# --------------------------------------------------------------------------
# knob plumbing: resolve, Fidelity JSON, engine registry
# --------------------------------------------------------------------------

def test_resolve_use_kernel(monkeypatch):
    assert kn.resolve_use_kernel(True) is True
    assert kn.resolve_use_kernel(False) is False
    assert kn.resolve_use_kernel("on") is True
    assert kn.resolve_use_kernel("off") is False
    assert kn.resolve_use_kernel("auto") is True
    monkeypatch.setenv("SPAC_NETSIM_KERNEL", "off")
    assert kn.resolve_use_kernel("auto") is False
    assert kn.resolve_use_kernel("on") is True     # explicit on still wins
    with pytest.raises(ValueError):
        kn.resolve_use_kernel("sometimes")


def test_use_kernel_fidelity_json_roundtrip():
    from repro.api.scenario import Fidelity, Scenario
    from repro.api import registry

    fid = Fidelity(use_kernel="on")
    assert Fidelity.from_dict(json.loads(json.dumps(fid.to_dict()))) == fid
    # bools normalise to the canonical strings
    assert Fidelity(use_kernel=True).use_kernel == "on"
    assert Fidelity(use_kernel=False).use_kernel == "off"
    with pytest.raises(ValueError):
        Fidelity(use_kernel="sometimes")
    # whole-scenario JSON round-trip preserves the knob
    scn = registry["hft"].override(use_kernel="off")
    assert scn.fidelity.use_kernel == "off"
    back = Scenario.from_json(scn.to_json())
    assert back.fidelity.use_kernel == "off"
    assert back == scn


def test_kernel_rungs_registered():
    from repro.sim.engines import get_engine

    spec = get_engine("batched_netsim[kernel]")
    assert spec.rung == 3 and spec.batched
    tr = hft(seed=0).head(256)
    cands = _sized_candidates()[:3]
    vk = get_engine("batched_netsim[kernel]").evaluate_batch(
        cands, BOUND, tr, back_annotation=False)
    vo = get_engine("batched_netsim").evaluate_batch(
        cands, BOUND, tr, back_annotation=False)
    _assert_results_identical(vk, vo)


def test_problem_rejects_unknown_use_kernel():
    tr = hft(seed=0).head(64)
    with pytest.raises(ValueError, match="use_kernel"):
        SwitchDSEProblem(ArchRequest(n_ports=8, addr_bits=4), BOUND, tr,
                         use_kernel="banana")


# --------------------------------------------------------------------------
# fixed-point sweeps (stage 2, round 1) vs the per-event port scans
# --------------------------------------------------------------------------

def _port_with_one_event():
    """hft with event 5 moved onto a ninth port, in and out: both its
    predecessors are the zero row, and it is no one's predecessor."""
    tr = hft(seed=0)
    src, dst = tr.src.copy(), tr.dst.copy()
    src[5] = dst[5] = 8
    return Trace("alone", tr.time_s, src, dst, tr.payload_bytes, 9,
                 tr.link_gbps)


#: trace, candidate rows, service rate as a multiple of the line rate
SWEEP_CASES = {
    "datacenter": (lambda: datacenter(seed=0), 40, (1.0, 4.0)),
    "hft": (lambda: hft(seed=0), 24, (2.0, 4.0)),
    # 0.95 of line rate in bursts: a busy chain longer than SWEEP_CAP
    "saturated": (lambda: hft(seed=0, load=0.95), 6, (1.0, 1.0)),
    "one_row": (lambda: datacenter(seed=1), 1, (1.0, 1.0)),
    "port_with_one_event": (_port_with_one_event, 5, (2.0, 4.0)),
}


def _service(wire_bytes, link_gbps, rows, speed):
    rate = link_gbps * 1e9 * np.random.default_rng(0).uniform(*speed, rows)
    return wire_bytes[None, :] * 8.0 / rate[:, None]        # [rows, m]


def _round1_scan(now, src, dst, svc_t, pipe, n_ports):
    """The per-event round-1 port scan the sweeps replaced."""
    import jax
    import jax.numpy as jnp

    def step(carry, xs):
        in_f, out_f = carry
        tk, i, j, s = xs
        start = jnp.maximum(jnp.maximum(tk + pipe, in_f[:, i]), out_f[:, j])
        end = start + s
        return (in_f.at[:, i].set(end), out_f.at[:, j].set(end)), end

    zeros = jnp.zeros((svc_t.shape[1], n_ports), svc_t.dtype)
    return jax.lax.scan(step, (zeros, zeros), (now, src, dst, svc_t))[1].T


@pytest.mark.parametrize("stage", ["stage2", "round1"])
@pytest.mark.parametrize("case", sorted(SWEEP_CASES))
def test_sweeps_match_port_scan_bitwise(case, stage):
    """The sweep form inside ``surrogate.engine`` and
    ``netsim.kernel.round1`` is bit for bit the serial scan, whether it
    settles within ``SWEEP_CAP`` sweeps or falls back to the scan."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.netsim.ops import _round1
    from repro.kernels.xbar import SWEEP_CAP
    from repro.kernels.xbar.ref import xbar_contend_abs_ref
    from repro.sim.batched_surrogate import _engine

    make, rows, speed = SWEEP_CASES[case]
    tr = make()
    n = tr.n_ports
    with jax.enable_x64():
        if stage == "stage2":
            tl = tlmod.stage2_timeline(tr, n)
            t, src, dst = (jnp.asarray(tl.t), jnp.asarray(tl.src, jnp.int32),
                           jnp.asarray(tl.dst, jnp.int32))
            svc = jnp.asarray(_service(tl.payload + 42, tr.link_gbps, rows,
                                       speed))
            got, _, sweeps, fell_back = _engine(
                jnp.asarray(tl.dt), src, dst, svc, t, jnp.ones(rows),
                n_ports=n, use_pallas=False, interpret=False)
            want = xbar_contend_abs_ref(t, src, dst, svc, n_ports=n)
        else:
            tl = tlmod.stage4_timeline(tr, n, 42, 0.0)
            now, src, dst = (jnp.asarray(tl.now),
                             jnp.asarray(tl.src_o, jnp.int32),
                             jnp.asarray(tl.dst_o, jnp.int32))
            svc_t = jnp.asarray(_service(tl.wire_e, tr.link_gbps, rows,
                                         speed).T)
            pipe = jnp.asarray(np.linspace(0.0, 5e-8, rows))
            c = tl.chain
            got, _, sweeps, fell_back = _round1(
                now, src, dst, svc_t, pipe, jnp.full(rows, 64, jnp.int32),
                jnp.asarray(c.perm, jnp.int32), jnp.asarray(c.seg_start),
                jnp.asarray(c.rank), n_ports=n)
            want = _round1_scan(now, src, dst, svc_t, pipe, n)
        got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape == (rows, len(tr))
    np.testing.assert_array_equal(got.view(np.int64), want.view(np.int64))
    if case == "saturated":
        assert (int(sweeps), int(fell_back)) == (SWEEP_CAP, 1)
    else:
        assert 1 <= int(sweeps) < SWEEP_CAP and int(fell_back) == 0


# --------------------------------------------------------------------------
# mesh x kernel composition (forced host devices, subprocess)
# --------------------------------------------------------------------------

def test_mesh_kernel_composition_bit_identical():
    from tests.test_mesh_dse import _require_forced_devices, _run

    _require_forced_devices()
    _run("""
import numpy as np
from repro.core import ArchRequest, bind, compressed_protocol, enumerate_candidates
from repro.launch.mesh import MeshSpec
from repro.sim import run_netsim_batched, run_surrogate_batched
from repro.traces import hft

BOUND = bind(compressed_protocol(addr_bits=4, length_bits=6), flit_bits=256)
tr = hft(seed=0)
cands = [a.with_depth(d) for a in
         enumerate_candidates(ArchRequest(n_ports=8, addr_bits=4))[:7]
         for d in (2, 64)]                   # depth 2 drops -> subset iteration
base = run_netsim_batched(cands, BOUND, tr, back_annotation=False,
                          use_kernel=True)
assert any(v.drop_rate > 0 for v in base)
for d in (2, 8):
    got = run_netsim_batched(cands, BOUND, tr, back_annotation=False,
                             use_kernel=True, mesh=MeshSpec(devices=d))
    for vb, vr in zip(base, got):
        assert vb.p99_latency_ns == vr.p99_latency_ns
        assert vb.drop_rate == vr.drop_rate
        assert vb.throughput_gbps == vr.throughput_gbps
        np.testing.assert_array_equal(vb.meta["latency_ns"],
                                      vr.meta["latency_ns"])
    s = run_surrogate_batched(cands, BOUND, tr, back_annotation=False,
                              mesh=MeshSpec(devices=d))
    s0 = run_surrogate_batched(cands, BOUND, tr, back_annotation=False)
    np.testing.assert_array_equal(s0.q_occupancy, s.q_occupancy)
    np.testing.assert_array_equal(s0.latency_ns, s.latency_ns)
    print("devices", d, "kernel bit-identical OK")
""")
