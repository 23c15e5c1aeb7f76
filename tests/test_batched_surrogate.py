"""Batched JAX surrogate engine vs the serial reference (stage-2 fan-out).

Acceptance contract: on the same candidates + trace the two paths must agree
*exactly* on occupancy-derived drop counts and within rtol 1e-3 on latency
quantiles, across the hft and datacenter workloads and both VOQ kinds, for a
>= 32 candidate batch — and ``run_dse`` must produce the same Pareto front
through either path.
"""

import numpy as np
import pytest

from repro.core import (ArchRequest, ResourceBudget, SLA, SchedulerKind,
                        SwitchArch, ForwardTableKind, VOQKind, bind,
                        compressed_protocol, enumerate_candidates, run_dse)
from repro.core.dse import DSEProblem
from repro.sim import run_surrogate, run_surrogate_batched
from repro.sim.resources import ALVEO_U45N
from repro.sim.switch_problem import SwitchDSEProblem
from repro.traces import datacenter, hft

BOUND = bind(compressed_protocol(addr_bits=4, length_bits=6), flit_bits=256)


def _traces():
    return {
        "hft": hft(seed=0),
        "datacenter": datacenter(seed=0, n_ports=8, duration_s=200e-6),
    }


def _candidates():
    cands = enumerate_candidates(ArchRequest(n_ports=8, addr_bits=4))
    assert len(cands) >= 32
    assert {a.voq for a in cands} == {VOQKind.NXN, VOQKind.SHARED}
    return cands


@pytest.mark.parametrize("workload", ["hft", "datacenter"])
def test_batched_matches_serial_surrogate(workload):
    tr = _traces()[workload]
    cands = _candidates()
    batch = run_surrogate_batched(cands, BOUND, tr, back_annotation=False)
    serial = [run_surrogate(a, BOUND, tr, back_annotation=False) for a in cands]
    for arch, rb, rs in zip(cands, batch.results(), serial):
        # occupancy samples (and hence drop counts at ANY depth) exact
        np.testing.assert_array_equal(rb.q_occupancy, rs.q_occupancy,
                                      err_msg=arch.short())
        for depth in (4, 16, 64):
            assert int((rb.q_occupancy > depth).sum()) == \
                   int((rs.q_occupancy > depth).sum())
        # latency quantiles within tolerance
        for q in (50.0, 99.0):
            assert rb.p(q) == pytest.approx(rs.p(q), rel=1e-3)
        assert rb.throughput_gbps == pytest.approx(rs.throughput_gbps, rel=1e-6)
        assert rb.meta["line_rate_feasible"] == rs.meta["line_rate_feasible"]
        if arch.voq is VOQKind.SHARED:
            np.testing.assert_array_equal(rb.meta["shared_occupancy"],
                                          rs.meta["shared_occupancy"])


def test_float64_path_is_bitwise_exact():
    """The absolute-time f64 scan reproduces the serial recurrence verbatim."""
    tr = hft(seed=1)
    cands = _candidates()[:8]
    batch = run_surrogate_batched(cands, BOUND, tr, back_annotation=False)
    for a, rb in zip(cands, batch.results()):
        rs = run_surrogate(a, BOUND, tr, back_annotation=False)
        np.testing.assert_array_equal(rb.latency_ns, rs.latency_ns)


def test_batched_summary_arrays():
    tr = hft(seed=0)
    cands = _candidates()
    batch = run_surrogate_batched(cands, BOUND, tr, back_annotation=False,
                                  quantiles=(50.0, 90.0, 99.0))
    b, m = len(cands), len(tr)
    assert batch.latency_ns.shape == (b, m)
    assert batch.quantiles.shape == (b, 3)
    assert batch.throughput_gbps.shape == (b,)
    assert batch.peak_occupancy.shape == (b,)
    hist = batch.occupancy_hist()
    assert hist.shape[0] == b
    # every sample lands in a bin; clamp mirrors the engine's occ >= 0 floor
    assert (hist.sum(axis=1) == m).all()
    # quantiles agree with the per-candidate latency arrays
    np.testing.assert_allclose(
        batch.quantiles[:, 2],
        np.percentile(batch.latency_ns, 99.0, axis=1), rtol=1e-12)


def test_mixed_port_batches_are_partitioned():
    tr = hft(seed=0)
    mixed = (enumerate_candidates(ArchRequest(n_ports=8, addr_bits=4))[:3]
             + enumerate_candidates(ArchRequest(n_ports=4, addr_bits=4))[:3])
    batch = run_surrogate_batched(mixed, BOUND, tr, back_annotation=False)
    for a, rb in zip(mixed, batch.results()):
        rs = run_surrogate(a, BOUND, tr, back_annotation=False)
        np.testing.assert_array_equal(rb.q_occupancy, rs.q_occupancy)


def test_empty_batch():
    assert run_surrogate_batched([], BOUND, hft(seed=0)).results() == []


def test_empty_trace():
    from repro.traces.base import Trace
    empty = Trace("empty", np.zeros(0), np.zeros(0, np.int32),
                  np.zeros(0, np.int32), np.zeros(0, np.int64), 8)
    batch = run_surrogate_batched(_candidates()[:4], BOUND, empty,
                                  back_annotation=False)
    assert batch.latency_ns.shape == (4, 0)
    for r in batch.results():
        assert r.q_occupancy.size == 0


def test_use_pallas_coerces_precision():
    """The Pallas kernel is float32 by design; requesting it must not pretend
    the bit-exact float64 contract still holds."""
    tr = hft(seed=0).head(64)
    batch = run_surrogate_batched(_candidates()[:2], BOUND, tr,
                                  back_annotation=False, use_pallas=True,
                                  interpret=True)
    assert batch.meta["precision"] == "float32"


def test_misaligned_hw_list_raises():
    with pytest.raises(ValueError, match="index-aligned"):
        run_surrogate_batched(_candidates()[:4], BOUND, hft(seed=0),
                              hw=[None, None])


def test_xbar_absolute_requires_f64():
    import jax.numpy as jnp
    from repro.kernels.xbar import xbar_contend
    m, b, n = 8, 2, 4
    z32 = jnp.zeros((m,), jnp.float32)
    svc = jnp.ones((b, m), jnp.float32)
    idx = jnp.zeros((m,), jnp.int32)
    with pytest.raises(ValueError, match="float64"):
        xbar_contend(z32, z32, idx, idx, svc, n_ports=n, absolute=True)


def test_surrogate_batch_misalignment_raises():
    class Broken(SwitchDSEProblem):
        def surrogate_batch(self, archs):
            return super().surrogate_batch(archs)[:-1]   # drops one result

    tr = hft(seed=0)
    prob = Broken(ArchRequest(n_ports=8, addr_bits=4), BOUND, tr,
                  back_annotation=False)
    with pytest.raises(ValueError, match="index-aligned"):
        run_dse(prob, SLA(p99_latency_ns=5000, drop_rate=1e-3),
                ResourceBudget(dict(ALVEO_U45N)))


@pytest.mark.parametrize("m,b,n,seed,horizon", [
    (160, 10, 8, 3, 1e-5),
    # two candidate blocks, three event blocks (the last padded): port
    # slacks must carry across event blocks
    (2500, 130, 32, 9, 3e-5)], ids=["one_block", "across_blocks"])
def test_pallas_xbar_matches_slack_oracle(m, b, n, seed, horizon):
    import jax.numpy as jnp
    from repro.kernels.xbar import xbar_contend
    from repro.kernels.xbar.ref import xbar_contend_slack_ref

    rng = np.random.default_rng(seed)
    t = np.sort(rng.uniform(0, horizon, m))
    dt = np.diff(t, prepend=t[:1]).astype(np.float32)
    src = rng.integers(0, n, m).astype(np.int32)
    dst = rng.integers(0, n, m).astype(np.int32)
    svc = np.abs(rng.normal(1e-8, 2e-9, (b, m))).astype(np.float32)
    ref = xbar_contend_slack_ref(jnp.asarray(dt), jnp.asarray(src),
                                 jnp.asarray(dst), jnp.asarray(svc), n_ports=n)
    pal, _, _ = xbar_contend(jnp.asarray(t, jnp.float32), jnp.asarray(dt),
                             jnp.asarray(src), jnp.asarray(dst),
                             jnp.asarray(svc), n_ports=n, use_pallas=True,
                             interpret=True)
    np.testing.assert_array_equal(np.asarray(pal), np.asarray(ref))


def test_float32_precision_mode_within_tolerance():
    tr = hft(seed=0)
    cands = _candidates()[:8]
    b32 = run_surrogate_batched(cands, BOUND, tr, back_annotation=False,
                                precision="float32")
    for a, rb in zip(cands, b32.results()):
        rs = run_surrogate(a, BOUND, tr, back_annotation=False)
        for q in (50.0, 99.0):
            assert rb.p(q) == pytest.approx(rs.p(q), rel=1e-3)


class _SerialSwitchProblem(SwitchDSEProblem):
    """The same problem forced through the serial stage-2 fallback."""
    surrogate_batch = DSEProblem.surrogate_batch


def test_run_dse_pareto_front_identical_batched_vs_serial():
    tr = hft(seed=0)
    req = ArchRequest(n_ports=8, addr_bits=4)
    sla = SLA(p99_latency_ns=5000, drop_rate=1e-3)
    budget = ResourceBudget(dict(ALVEO_U45N))
    res_b = run_dse(SwitchDSEProblem(req, BOUND, tr, back_annotation=False),
                    sla, budget)
    res_s = run_dse(_SerialSwitchProblem(req, BOUND, tr, back_annotation=False),
                    sla, budget)
    assert sorted(a.short() for a, _ in res_b.pareto) == \
           sorted(a.short() for a, _ in res_s.pareto)
    assert res_b.best.short() == res_s.best.short()
    assert [lg.survived for lg in res_b.logs] == \
           [lg.survived for lg in res_s.logs]


def _comm_step_time_scalar(prob, c):
    """Independent scalar reference for the analytic fabric model (the
    pre-vectorisation formulas, kept here so the parity test does not become
    a tautology now that ``surrogate`` delegates to ``surrogate_batch``)."""
    slots = prob.tokens_per_device * prob.cfg.moe_topk * c.capacity_factor
    slot = prob.cfg.d_model * (1 if c.payload == "int8" else 2)
    a2a = 2.0 * slots * slot * ((prob.tp_size - 1) / prob.tp_size)
    t_compute = 3 * 2 * slots * prob.cfg.d_model * prob.cfg.d_ff \
        / prob.hw["peak_flops_bf16"]
    t_wire = a2a / prob.hw["ici_link_gbps"]
    n_chunks = max(c.a2a_chunks, 1)
    t_issue = 5e-6 * n_chunks
    if n_chunks > 1:
        per = max(t_compute, t_wire) / n_chunks
        return per * (n_chunks + 1) + t_issue, a2a
    return t_compute + t_wire + t_issue, a2a


def test_comm_surrogate_batch_matches_scalar_reference():
    """The vectorised analytic fabric model matches an independent scalar
    re-derivation of the formulas, per candidate."""
    jax = pytest.importorskip("jax")
    from repro.comm.dse_comm import CommDSEProblem
    from repro.models.config import ModelConfig, ShardingPlan
    from repro.models.moe import init_moe
    from repro.launch.mesh import make_mesh

    cfg = ModelConfig(name="t", family="moe", n_layers=1, d_model=128,
                      n_heads=4, n_kv_heads=2, d_ff=256, vocab=256,
                      moe_experts=8, moe_topk=2)
    plan = ShardingPlan()
    params, _ = init_moe(jax.random.PRNGKey(0), cfg, plan)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 128))
    mesh = make_mesh((1, 1), ("data", "model"))
    prob = CommDSEProblem(params, cfg, plan, mesh, x, model_tp=8)
    cands = prob.candidates()
    assert len(cands) >= 8
    expected_occ = prob.loads.reshape(-1) / max(prob.loads.mean(), 1e-9)
    results = prob.surrogate_batch(cands)
    assert len(results) == len(cands)
    for c, sb in zip(cands, results):
        t_ref, a2a_ref = _comm_step_time_scalar(prob, c)
        np.testing.assert_array_equal(sb.q_occupancy, expected_occ)
        np.testing.assert_allclose(sb.latency_ns, np.full(16, t_ref * 1e9),
                                   rtol=1e-12)
        assert sb.throughput_gbps == pytest.approx(
            a2a_ref * 8 / max(t_ref, 1e-12) / 1e9, rel=1e-12)
        # the serial hook is the same body at batch size 1
        ss = prob.surrogate(c)
        np.testing.assert_array_equal(sb.q_occupancy, ss.q_occupancy)
        assert ss.q_occupancy is not sb.q_occupancy   # no cross-result aliasing
